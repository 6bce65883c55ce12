"""Whole prefill: model FLOPs of the prompt tokens the traced prefills
computed (``trace.prefill_flops``, counted by the cell's architecture
module) over the summed time of those prefills (the
``chipbench.prefill`` spans of the profiler trace) times the chip's peak
FLOP/s, in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("peaks") or not tr["prefill_s"]:
        return None
    return 100.0 * tr["prefill_flops"] / (tr["prefill_s"]
                                          * record["peaks"]["flops_per_s"])
