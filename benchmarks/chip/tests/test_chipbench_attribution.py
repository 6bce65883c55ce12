"""``attribution``: the clock fit from two anchors, the split of the
device's idle time by the innermost program span, the numbers it reads
(``step_host_ms``, ``prefill_idle_ms``, ``kv_used_share`` from the step
spans), a real profile's anchors on the CPU, and one traced run of a
tiny cell through the command."""

import json
import os

import pytest

from chipbench_setup import checkout  # noqa: F401  (a fixture)
import attribution
import run
from repro import obs
from repro.core.materializer import MeshSpec

MESH = MeshSpec.of_chip("test", (1, 1), ("data", "model"), "TPU v5 lite")


def mapped(t):
    """A profiler clock 5 s ahead of ``perf_counter`` that runs 20 ppm
    fast, in nanoseconds."""
    return 5e9 + 1.00002e9 * t


def anchor_pair(t):
    """An anchor at ``t``: the ring's (t_in, t_out) 4 us apart, and the
    annotation 1 us inside them on the trace's clock."""
    t_in, t_out = t - 2e-6, t + 2e-6
    return (t_in, t_out), (mapped(t_in) + 1000.0, mapped(t_out) - 1000.0)


def test_two_anchors_recover_offset_and_drift():
    (r1, a1), (r2, a2) = anchor_pair(100.0), anchor_pair(108.0)
    fit = attribution.fit_clock([r1, r2], [a1, a2])
    assert fit.rate == pytest.approx(1.00002e9, rel=1e-12)
    assert fit.drift_ppm == pytest.approx(20.0, abs=1e-3)
    for t in (100.0, 104.0, 108.0, 250.0):
        assert fit(t) == pytest.approx(mapped(t), abs=1e-3)
    # each annotation edge lies 1 us inside the mapped bracket
    assert fit.residual_ns == pytest.approx(1000.0, abs=1e-3)
    # one anchor: the offset alone; none, or a pairing that does not
    # match, gives no fit
    one = attribution.fit_clock([r1], [a1])
    assert one.rate == 1e9 and one(100.0) == pytest.approx(mapped(100.0))
    assert attribution.fit_clock([], []) is None
    assert attribution.fit_clock([r1, r2], [a1]) is None


#: device busy in [0, 100]: idle is [0, 10], [20, 30], [60, 80], [90, 100]
BUSY = [(30, 45), (10, 20), (40, 60), (80, 90)]
SPANS = [(5, 70, "engine/step"),
         (25, 35, "runner/token_fetch"),      # inside the step
         (62, 66, "engine/retire"),           # inside the step
         (63, 64, "host/gc"),                 # inside the retire span
         (95, 95, "engine/admit_pass")]       # empty: covers nothing


def test_idle_goes_to_the_innermost_span_and_sums_to_the_total():
    parts = attribution.attribute_idle(BUSY, SPANS, 0, 100)
    assert parts == {"outside program spans": 5 + 10 + 10,
                     "engine/step": 5 + 5 + 2 + 4,
                     "runner/token_fetch": 5,
                     "engine/retire": 1 + 2,
                     "host/gc": 1}
    assert sum(parts.values()) == 50
    # the stretch cuts the gaps at its ends
    assert attribution.attribute_idle(BUSY, SPANS, 15, 65) == {
        "engine/step": 5 + 2, "runner/token_fetch": 5,
        "engine/retire": 1 + 1, "host/gc": 1}
    # no spans: all idle is outside them; no busy time: all is idle
    assert attribution.attribute_idle(BUSY, [], 0, 100) == {
        "outside program spans": 50}
    assert sum(attribution.attribute_idle([], SPANS, 0, 100).values()) \
        == 100


def test_step_host_ms_leaves_out_the_token_fetches():
    spans = [(1.0, 1.1, "engine/step"),
             (1.05, 1.08, "runner/token_fetch"),
             (1.2, 1.25, "engine/step"),
             (1.21, 1.22, "runner/first_token_fetch"),
             (1.23, 1.24, "runner/token_fetch"),
             (1.22, 1.23, "runner/decode_inputs"),   # host work: kept
             (1.3, 1.4, "engine/step"),
             (5.0, 5.1, "engine/step")]              # after the window
    # own times 70, 30 and 100 ms: the median is 70
    assert attribution.step_host_ms(spans, 1.0, 2.0) == pytest.approx(70.0)
    assert attribution.step_host_ms(spans, 3.0, 4.0) is None


def test_prefill_idle_ms_per_prefill_in_the_stretch():
    spans = [(5, 35, "request/prefill"),     # idle [5, 10] + [20, 30]
             (50, 70, "request/prefill"),    # idle [60, 70]
             (95, 105, "request/prefill"),   # leaves the stretch
             (0, 100, "engine/step")]
    v = attribution.prefill_idle_ms(BUSY, spans, 0, 100)
    assert v == pytest.approx((15 + 10) / 2 / 1e6)
    assert attribution.prefill_idle_ms(BUSY, spans[3:], 0, 100) is None


def ring_event(ts, dur, cat, name, args=None):
    return (ts, dur, "X" if dur else "i", cat, name, None, args)


def test_kv_used_share_from_the_step_spans_in_the_window():
    def step(ts, used):
        return ring_event(ts, 0.01, "engine", "step",
                          {"pages_used": used, "pool_pages": 200})
    events = [step(0.5, 200),                       # before the window
              step(1.0, 50), step(1.2, 100),
              ring_event(1.3, 0.01, "engine", "retire", {"finished": 1}),
              step(1.4, 150),
              step(2.5, 0)]                         # after it
    assert attribution.step_kv_used_share(events, 1.0, 2.0) \
        == pytest.approx(50.0)
    assert attribution.step_kv_used_share(events, 3.0, 4.0) is None


def test_attribute_maps_the_ring_onto_the_trace():
    (r1, a1), (r2, a2) = anchor_pair(100.0), anchor_pair(108.0)
    # a 100 ms step at 101 s whose last 40 ms wait on the device, which
    # is busy from 101.02 to 101.12 s on the trace's clock
    events = [ring_event(r1[0], 0.0, "obs", "clock",
                         {"t_in": r1[0], "t_out": r1[1]}),
              ring_event(101.0, 0.1, "engine", "step"),
              ring_event(101.06, 0.04, "runner", "token_fetch"),
              ring_event(r2[0], 0.0, "obs", "clock",
                         {"t_in": r2[0], "t_out": r2[1]})]
    busy = [(mapped(101.02), mapped(101.12))]
    got = attribution.attribute(events, [busy], [a1, a2])
    assert got["residual_ms"] == pytest.approx(1e-3, abs=1e-9)
    assert got["drift_ppm"] == pytest.approx(20.0, abs=1e-3)
    idle = got["idle_s"]
    assert idle["engine/step"] == pytest.approx(0.02 * 1.00002, rel=1e-6)
    assert "runner/token_fetch" not in idle
    stretch = (a2[0] - a1[1]) / 1e9
    assert got["stretch_s"] == pytest.approx(stretch)
    assert sum(idle.values()) == pytest.approx(stretch - 0.1 * 1.00002)
    assert got["idle_share"] == pytest.approx(
        100 * sum(idle.values()) / stretch)
    assert got["prefill_idle_ms"] is None
    # one anchor on the trace is not enough
    assert attribution.attribute(events, [busy], [a1]) is None


def test_anchors_of_a_real_profile_on_the_cpu(tmp_path):
    """The annotations ``Tracer.anchor`` places are read back from a
    profile, and the fit through them is tight."""
    import jax
    t = obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.anchor()
        jax.numpy.ones(8).block_until_ready()
        t.anchor()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    path = attribution.trace_reduce.find_trace(str(tmp_path))
    devices, anchors = attribution.read_trace(path)
    assert devices == [] and len(anchors) == 2
    fit = attribution.fit_clock(attribution.ring_anchors(t.snapshot()),
                                anchors)
    assert fit.residual_ns < 1e6


def test_traced_run_of_a_tiny_cell(checkout, capsys, monkeypatch,  # noqa: F811
                                   tmp_path):
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = attribution.main(
        ["--workload", "tiny-long", "--seed", str(2**31 + 5), "--seconds",
         "4"], require_tpu=False, mesh=MESH, root=checkout,
        bench_dir=os.path.join(checkout, "benchmarks", "chip"),
        persistent_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert json.loads(out[-2])["correct"]
    table = json.loads(out[-1])
    assert table["correct"]
    # the CPU has no device plane: the whole anchored stretch is idle,
    # and most of it lies inside the program's spans
    assert table["residual_ms"] < 1.0
    assert table["idle_share"] == pytest.approx(100.0)
    assert sum(table["idle_s"].values()) == pytest.approx(
        table["stretch_s"])
    assert table["idle_s"]["engine/step"] > 0
    assert table["step_host_ms"] > 0
    # the program's step boundary reads the pool as the harness does
    kv = table["kv_used_share"]
    assert kv["program"] == pytest.approx(kv["harness"], abs=1.0)
    assert set(table["end_to_end"]) == {"itl_p95_ms", "output_tok_s",
                                        "peak_hbm_gb", "setup_s"}
    assert obs.current() is None
