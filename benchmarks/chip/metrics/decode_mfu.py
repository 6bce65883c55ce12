"""Whole decode step: model FLOPs of the tokens the traced decode steps
made (``kernel_work.decode_flops``) over the summed time of those steps
(the ``chipbench.decode`` spans of the profiler trace) times the chip's
peak FLOP/s, in percent."""

import kernel_work


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("peaks") or not tr["decode_s"]:
        return None
    m = record["model"]
    flops = sum(kernel_work.decode_flops(m, lens) for lens in tr["decode_lens"])
    return 100.0 * flops / (tr["decode_s"] * record["peaks"]["flops_per_s"])
