"""Prefix cache: the share of prompt tokens served from cached pages
(the request's ``cached_len`` at admission, a program counter), over
requests admitted in the window, in percent."""


def read(record):
    t0, t1 = record["window"]
    rows = [r for r in record["requests"]
            if r["cached_len"] is not None and r["stamps"]
            and t0 <= r["stamps"][0] <= t1]
    total = sum(r["prompt_len"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["cached_len"] for r in rows) / total
