"""The metric arithmetic, on a hand-made record of a run: every tail is
taken over all the samples of the window, and each reader finds the
numbers where the harness puts them."""

import pytest

import chipbench_setup  # noqa: F401  (the benchmark and src on the path)
import registry
import run
import tails

DENSE = registry.arch({"name": "t", "model_type": "mistral"})

MODEL = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 512}
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e9}


def req(rid, due, stamps, prompt=100, cached=0, failed=False):
    return {"id": rid, "due": due, "stamps": stamps, "prompt_len": prompt,
            "cached_len": cached, "failed": failed}


@pytest.fixture
def record():
    # window [10, 20], nominal end 20; times in seconds
    trace = {"busy_s": 1.5, "window_s": 2.0, "kernel_s": 0.001,
             "decode_lens": [[100, 300]], "decode_s": 0.5,
             "prefill_s": 0.25, "prefills": [(200, 150)]}
    run.add_work(trace, DENSE, MODEL)
    return {
        "window": (10.0, 20.0), "nominal_end": 20.0, "setup_s": 42.0,
        "memory_peak_bytes": 12_345_000_000, "model": MODEL,
        "peaks": PEAKS,
        "requests": [
            # set-up request: its first gap (9.5 -> 10.5) straddles the
            # window's start and is not counted
            req("a", 9.0, [9.5, 10.5, 10.7]),
            req("b", 11.0, [11.2, 11.3, 11.6], prompt=200, cached=150),
            req("c", 12.0, [12.5, 12.5, 13.0, 13.1]),
            req("d", 19.0, [], failed=False),       # no first token
            req("e", 19.5, [], failed=True),        # rejected
            req("f", 20.5, [21.0]),                 # due after the window
        ],
        "steps": [(10.0, 10.5, 10, 100), (10.5, 11.0, 30, 100),
                  (25.0, 26.0, 99, 100)],
        "obs": {
            "admit": {"b": 11.1, "c": 12.3, "a": 9.2},
            "prefill": [{"t0": 11.1, "dur": 0.05, "computed": 50},
                        {"t0": 12.3, "dur": 0.15, "computed": 100}],
            "decode_step": [{"t0": 10.0, "dur": 0.02, "batch": 2},
                            {"t0": 10.1, "dur": 0.04, "batch": 4},
                            {"t0": 10.2, "dur": 0.03, "batch": 3}],
        },
        "trace": trace,
    }


def read(name, record):
    return registry.metric_reader(name)(record)


def test_percentile_is_nearest_rank_over_all_samples():
    xs = list(range(1, 101))
    assert tails.nearest_rank(xs, 90) == 90
    assert tails.nearest_rank(xs, 95) == 95
    assert tails.nearest_rank([5.0], 99) == 5.0
    assert tails.nearest_rank([], 50) is None
    # a tail of all samples, not a median of per-part tails
    parts = [[1, 2, 3, 100], [1, 2, 3, 4]]
    assert tails.nearest_rank(sum(parts, []), 90) == 100


def test_ttft_counts_due_time_and_ranks_missing_above_served(record):
    s = sorted(tails.ttft_samples(record))
    # b: 0.2, c: 0.5; d and e have no first token and rank above
    assert s[:2] == pytest.approx([0.2, 0.5])
    assert s[2] == pytest.approx(0.5 + 0.5) and s[3] == pytest.approx(
        0.5 + 1.0)
    assert read("ttft_p85_ms", record) == pytest.approx(1500.0)


def test_itl_gaps_inside_the_window(record):
    gaps = sorted(tails.itl_samples(record))
    assert gaps == pytest.approx(sorted([0.2, 0.1, 0.3, 0.0, 0.5, 0.1]))
    assert read("itl_p95_ms", record) == pytest.approx(500.0)


def test_output_rate_and_memory_and_setup(record):
    # tokens stamped in [10, 20]: a 2, b 3, c 4
    assert read("output_tok_s", record) == pytest.approx(9 / 10.0)
    assert read("peak_hbm_gb", record) == pytest.approx(12.345)
    assert read("setup_s", record) == 42.0


def test_layer_metrics(record):
    # queue waits of b and c (a was due before the window)
    assert read("queue_wait_p90_ms", record) == pytest.approx(300.0)
    # b and c admitted in the window: 150 of 300 prompt tokens cached
    assert read("prefix_hit_rate", record) == pytest.approx(50.0)
    assert read("prefill_ms_per_ktok", record) == pytest.approx(
        1e3 * 0.2 / 0.15)
    assert read("prefill_share", record) == pytest.approx(100 * 0.2 / 1.0)
    assert read("decode_step_ms", record) == pytest.approx(30.0)
    assert read("batch_occupancy_mean", record) == pytest.approx(3.0)
    assert read("kv_used_share", record) == pytest.approx(20.0)
    assert read("device_idle_share", record) == pytest.approx(25.0)


def test_device_metrics_from_the_trace(record):
    # the work the run counts with the cell's architecture module
    f, b = DENSE.KERNELS["paged_attention"](MODEL, [100, 300])
    assert f == 2 * 4 * 400 * 4 * 16
    assert b == 2 * (400 * 2 * 16 * 2 * 2 + 2 * 4 * 16 * 2 * 2)
    assert record["trace"]["kernel_work"] == {"paged_attention": [f, b]}
    least = max(f / 1e12, b / 1e9)
    assert read("paged_attn_roofline", record) == pytest.approx(
        100 * least / 0.001)
    d = DENSE.decode_flops(MODEL, [100, 300])
    assert record["trace"]["decode_flops"] == d
    assert read("decode_mfu", record) == pytest.approx(100 * d / 0.5e12)
    p = DENSE.prefill_flops(MODEL, 150, 200)
    assert record["trace"]["prefill_flops"] == p
    assert read("prefill_mfu", record) == pytest.approx(100 * p / 0.25e12)


def test_device_metrics_silent_without_a_trace_or_peaks(record):
    no_trace = dict(record, trace=None)
    no_peaks = dict(record, peaks=None)
    for name in ("paged_attn_roofline", "decode_mfu", "prefill_mfu",
                 "device_idle_share"):
        assert read(name, no_trace) is None
        assert read(name, no_peaks) is None
