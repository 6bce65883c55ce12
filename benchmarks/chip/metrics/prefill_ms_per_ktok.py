"""Runner, prefill: milliseconds of ``repro.obs`` ``prefill`` span per
thousand prompt tokens computed (prompt less cached tokens), over the
prefills of the window."""


def read(record):
    spans = [s for s in record["obs"].get("prefill", [])
             if record["window"][0] <= s["t0"] <= record["window"][1]]
    toks = sum(s["computed"] for s in spans)
    if not toks:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / (toks / 1e3)
