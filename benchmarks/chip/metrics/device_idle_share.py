"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (traced window), from the profiler trace, in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("peaks") or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
