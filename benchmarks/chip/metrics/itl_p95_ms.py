"""95th percentile gap between consecutive output tokens of one request
(host clock, stamped after every step), over every gap in the window."""

from tails import itl_samples, nearest_rank


def read(record):
    v = nearest_rank(itl_samples(record), 95.0)
    return None if v is None else 1e3 * v
