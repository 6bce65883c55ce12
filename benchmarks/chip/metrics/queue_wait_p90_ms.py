"""Router + admission: 90th percentile of the time from each request's
due time to its ``admit`` event in ``repro.obs`` (program span), over
requests due in the window and admitted in it."""

from tails import due_in_window, nearest_rank


def read(record):
    admit = record["obs"].get("admit", {})
    waits = [admit[r["id"]] - r["due"] for r in due_in_window(record)
             if r["id"] in admit]
    v = nearest_rank(waits, 90.0)
    return None if v is None else 1e3 * v
