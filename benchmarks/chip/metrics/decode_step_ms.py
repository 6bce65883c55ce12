"""Runner, decode: median ``repro.obs`` ``decode_step`` span in the
window (it ends in the runner's one blocking token fetch, so it covers
the device work)."""

from tails import median


def read(record):
    t0, t1 = record["window"]
    v = median([s["dur"] for s in record["obs"].get("decode_step", [])
                if t0 <= s["t0"] <= t1])
    return None if v is None else 1e3 * v
