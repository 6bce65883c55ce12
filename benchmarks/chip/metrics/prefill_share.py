"""Engine: the share of the window's engine step time spent in
``repro.obs`` ``prefill`` spans, in percent."""


def read(record):
    t0, t1 = record["window"]
    step = sum(b - a for a, b, *_ in record["steps"] if t0 <= a <= t1)
    pre = sum(s["dur"] for s in record["obs"].get("prefill", [])
              if t0 <= s["t0"] <= t1)
    if not step:
        return None
    return 100.0 * pre / step
