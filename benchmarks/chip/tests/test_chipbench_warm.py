"""The warm-up's plan: the throwaway requests take every prefill shape a
prompt can reach, with and without the prefix cache, each prompt length
of the traffic among them, and one decode lands in each table-width
bucket."""

import pytest

import chipbench_setup  # noqa: F401  (puts the benchmark on the path)
from driver import next_pow2, prefill_keys, warm_plan

from repro.serving.kv_cache import PAGE_SIZE


def pages(n):
    return -(-n // PAGE_SIZE)


@pytest.mark.parametrize("cached,max_tokens,chunk_pages,lengths", [
    (True, 8192, 4, [1100, 1300, 2049, 2600, 4000, 7000, 8000]),
    (True, 1024, 4, [300, 400, 500, 700]),
    (False, 2048, 4, [130, 256, 400, 513, 900]),
    (False, 1024, 2, [16, 100, 300]),
])
def test_plan_covers_every_shape(cached, max_tokens, chunk_pages, lengths):
    plan, decodes = warm_plan(lengths, max_tokens, chunk_pages, cached)
    max_pages = max(pages(n) for n in lengths)
    starts = (lambda t: range(t)) if cached else (lambda t: range(1))
    covered = set()
    for n, s in plan:
        assert 0 <= s < pages(n) and n <= max_tokens
        covered |= prefill_keys(pages(n), s, chunk_pages, cached)
    for total in range(1, max_pages + 1):
        for s in starts(total):
            for k in prefill_keys(total, s, chunk_pages, cached):
                if k[0] != "slice" or any(pages(n) == total
                                          for n in lengths):
                    assert k in covered, (total, s, k)
    assert set(lengths) <= {n for n, _ in plan}
    widths = {next_pow2(p) for p in range(1, pages(max_tokens) + 1)}
    assert {next_pow2(pages(n + 2)) for n in decodes} == widths
    assert {next_pow2(pages(n + 1)) for n in decodes} == widths


def test_plan_is_cheap_with_the_prefix_cache():
    plan, _ = warm_plan(list(range(1000, 8000, 70)), 8192, 4, True)
    computed = sum(pages(n) - s for n, s in plan)
    assert computed <= 4 * len(plan)
