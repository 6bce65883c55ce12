"""Output tokens that reached the host in the window, per second of the
window (host clock; the window closes when its last step returns)."""

from tails import in_window


def read(record):
    t0, t1 = record["window"]
    n = sum(1 for r in record["requests"] for t in r["stamps"]
            if in_window(record, t))
    return n / (t1 - t0)
