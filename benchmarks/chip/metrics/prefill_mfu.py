"""Whole prefill: model FLOPs of the prompt tokens the traced prefills
computed (``kernel_work.prefill_flops``) over the summed time of those
prefills (the ``chipbench.prefill`` spans of the profiler trace) times
the chip's peak FLOP/s, in percent."""

import kernel_work


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("peaks") or not tr["prefill_s"]:
        return None
    m = record["model"]
    flops = sum(kernel_work.prefill_flops(m, cached, n)
                for n, cached in tr["prefills"])
    return 100.0 * flops / (tr["prefill_s"] * record["peaks"]["flops_per_s"])
