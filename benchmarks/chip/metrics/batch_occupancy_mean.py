"""Engine: mean ``batch`` of the window's ``repro.obs`` ``decode_step``
events (requests decoded per step)."""


def read(record):
    t0, t1 = record["window"]
    b = [s["batch"] for s in record["obs"].get("decode_step", [])
         if t0 <= s["t0"] <= t1]
    return sum(b) / len(b) if b else None
