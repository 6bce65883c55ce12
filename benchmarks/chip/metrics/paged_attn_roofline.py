"""Kernel: the paged-attention kernel's share of its roofline.  The least
time the chip could take for the traced decode steps' attention (the
larger of its operations over peak FLOP/s and its bytes over peak
bandwidth, ``kernel_work.paged_attention``) over the summed device time
of the kernel's events in the profiler trace, in percent."""

import kernel_work


def read(record):
    tr = record.get("trace")
    if (not tr or not record.get("peaks") or not tr["kernel_s"]
            or not tr["decode_lens"]):
        return None
    m = record["model"]
    flops = byts = 0
    for lens in tr["decode_lens"]:
        f, b = kernel_work.paged_attention(lens, m["heads"], m["kv_heads"],
                                           m["head_dim"])
        flops += f * m["layers"]
        byts += b * m["layers"]
    pk = record["peaks"]
    least = max(flops / pk["flops_per_s"], byts / pk["bytes_per_s"])
    return 100.0 * least / tr["kernel_s"]
