"""Kernel: the paged-attention kernel's share of its roofline.  The least
time the chip could take for the traced decode steps' attention (the
larger of its operations over peak FLOP/s and its bytes over peak
bandwidth, ``trace.kernel_work["paged_attention"]``, counted by the
cell's architecture module) over the summed device time of the kernel's
events in the profiler trace (``trace.kernel_s``), in percent."""


def read(record):
    tr = record.get("trace")
    if (not tr or not record.get("peaks") or not tr["kernel_s"]
            or not tr["decode_lens"]
            or "paged_attention" not in tr["kernel_work"]):
        return None
    flops, byts = tr["kernel_work"]["paged_attention"]
    pk = record["peaks"]
    least = max(flops / pk["flops_per_s"], byts / pk["bytes_per_s"])
    return 100.0 * least / tr["kernel_s"]
