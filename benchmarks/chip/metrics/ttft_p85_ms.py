"""85th percentile time to first token (host clock): from each request's
due time, not its submit time, to its first token on the host, over
every request due in the window; one that failed or had no first token
by the window's close ranks above every served request.  The 85th is the
highest percentile with ten requests beyond it in a window of
``nemo-chat-shared`` (70 to 90 requests due)."""

from tails import nearest_rank, ttft_samples


def read(record):
    v = nearest_rank(ttft_samples(record), 85.0)
    return None if v is None else 1e3 * v
