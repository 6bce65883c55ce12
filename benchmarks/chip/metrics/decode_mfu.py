"""Whole decode step: model FLOPs of the tokens the traced decode steps
made (``trace.decode_flops``, counted by the cell's architecture module)
over the summed time of those steps (the ``chipbench.decode`` spans of
the profiler trace) times the chip's peak FLOP/s, in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("peaks") or not tr["decode_s"]:
        return None
    return 100.0 * tr["decode_flops"] / (tr["decode_s"]
                                         * record["peaks"]["flops_per_s"])
