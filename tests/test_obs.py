"""The repro.obs observability plane: tracer ring semantics, histogram
math and windowed deltas, Prometheus exposition, both trace export
formats round-tripping through the loader, the CLI summary, and --
critically -- exact request-lifecycle reconstruction: the events an
instrumented run captures must agree with the engine's own counters,
and the whole plane must be a no-op when disabled."""

import gc
import json
import subprocess
import sys
import time

import pytest

from repro import obs
from repro.autoscale import MetricsWindow, stats_delta
from repro.core.history import HistoryStore
from repro.obs.metrics import (LATENCY_BOUNDS, OCCUPANCY_BOUNDS, Histogram,
                               hist_delta, hist_merge)
from repro.obs.summary import pctl, request_lifecycles, summarize
from repro.runtime import (Application, Cluster, JaxExecutor, NullExecutor,
                           ServeOptions)
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PAGE_SIZE, PagePool, Request


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with the plane disabled (the module
    globals are process-wide; a leak would instrument unrelated tests)."""
    obs.disable()
    obs.disable_metrics()
    yield
    obs.disable()
    obs.disable_metrics()


def _drive(n=6, prompt=48, gen=8, max_batch=4):
    """A small null-backend engine run; returns (engine, pool)."""
    pool = PagePool(64)
    eng = ServingEngine(pool, max_batch=max_batch)
    for i in range(n):
        eng.submit(Request(f"r{i}", prompt, gen))
    steps = 0
    while eng.step() and steps < 50_000:
        steps += 1
    return eng, pool


# ---------------------------------------------------------------------------
# tracer ring
# ---------------------------------------------------------------------------

def test_ring_bounds_and_drop_accounting():
    t = obs.enable(capacity=8)
    for i in range(20):
        t.instant("request", "submit", f"r{i}")
    assert len(t) == 8
    assert t.dropped == 12
    # oldest dropped, newest kept
    assert t.snapshot()[0][5] == "r12" and t.snapshot()[-1][5] == "r19"
    t.clear()
    assert len(t) == 0 and t.dropped == 0


def test_tracer_accessors_and_span():
    t = obs.enable()
    t.instant("pool", "grant", "a", {"pages": 2})
    t.span("request", "prefill", 1.0, 1.5, "r0", {"prompt_len": 32})
    t.instant("request", "finish", "r0")
    assert [e[4] for e in t.by_scope("r0")] == ["prefill", "finish"]
    (ev,) = t.by_name("prefill", "request")
    assert ev[2] == "X" and ev[1] == pytest.approx(0.5)
    assert t.by_name("grant")[0][6] == {"pages": 2}
    assert t.by_name("grant", "request") == []  # cat filter applies


def test_disabled_plane_emits_nothing():
    assert obs.current() is None and obs.current_metrics() is None
    eng, _ = _drive()              # instrumented code runs with plane off
    assert eng.stats.completed == 6
    assert obs.current() is None, "a run must not implicitly enable obs"


# ---------------------------------------------------------------------------
# histograms + registry
# ---------------------------------------------------------------------------

def test_histogram_observe_percentile_mean():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(106.5)
    assert h.counts == [1, 2, 1, 1]    # last bucket = overflow
    assert h.percentile(50) == 2.0     # upper-edge approximation
    assert h.percentile(99) == 4.0     # +inf clamps to last finite edge
    assert h.mean == pytest.approx(106.5 / 5)
    assert Histogram().percentile(50) == 0.0   # empty


def test_histogram_dict_roundtrip_merge_and_bounds_guard():
    a, b = Histogram(bounds=(1.0, 2.0)), Histogram(bounds=(1.0, 2.0))
    a.observe(0.5), b.observe(5.0)
    m = Histogram.from_dict(hist_merge([a.to_dict(), b.to_dict()]))
    assert m.count == 2 and m.counts == [1, 0, 1]
    with pytest.raises(ValueError, match="different"):
        a.merge(Histogram(bounds=(1.0, 3.0)))


def test_hist_delta_window_and_reset_clamp():
    cur = {"bounds": [1.0], "counts": [3, 2], "sum": 9.0, "count": 5}
    since = {"bounds": [1.0], "counts": [1, 1], "sum": 3.0, "count": 2}
    d = hist_delta(cur, since)
    assert d == {"bounds": [1.0], "counts": [2, 1], "sum": 6.0, "count": 3}
    # None baseline and bounds mismatch both pass cur through (a copy)
    assert hist_delta(cur, None) == cur and hist_delta(cur, None) is not cur
    assert hist_delta(cur, {"bounds": [2.0], "counts": [9, 9],
                            "sum": 0.0, "count": 18}) == cur
    # counter reset (since > cur in any bucket): clamp to cur, never
    # negative counts
    reset = hist_delta(cur, {"bounds": [1.0], "counts": [5, 0],
                             "sum": 1.0, "count": 5})
    assert reset["counts"] == [3, 2] and reset["count"] == 5


def test_registry_render_prometheus_format():
    m = obs.enable_metrics()
    m.inc("repro_requests_total", 3, app="a")
    m.set_gauge("repro_queue_len", 7, app="a")
    h = m.histogram("repro_ttft_seconds", bounds=(0.1, 1.0), app="a")
    h.observe(0.05), h.observe(0.5), h.observe(50.0)
    text = m.render()
    assert '# TYPE repro_ttft_seconds histogram' in text
    assert 'repro_requests_total{app="a"} 3' in text
    assert 'repro_queue_len{app="a"} 7' in text
    # cumulative le buckets, then +Inf == _count
    assert 'repro_ttft_seconds_bucket{app="a",le="0.1"} 1' in text
    assert 'repro_ttft_seconds_bucket{app="a",le="1"} 2' in text
    assert 'repro_ttft_seconds_bucket{app="a",le="+Inf"} 3' in text
    assert 'repro_ttft_seconds_count{app="a"} 3' in text
    assert m.app_histograms("a")["repro_ttft_seconds"]["count"] == 3
    assert m.app_histograms("nope") == {}
    # get-or-create returns the SAME object (hot paths hold it)
    assert m.histogram("repro_ttft_seconds", app="a") is h


# ---------------------------------------------------------------------------
# lifecycle reconstruction: trace events vs the engine's own counters
# ---------------------------------------------------------------------------

def test_trace_matches_engine_counters():
    t = obs.enable()
    m = obs.enable_metrics()
    eng, _ = _drive(n=6)
    s = eng.stats
    assert len(t.by_name("submit", "request")) == 6
    assert len(t.by_name("admit", "request")) == s.admitted
    assert len(t.by_name("finish", "request")) == s.completed == 6
    assert len(t.by_name("first_token", "request")) == s.ttft_count
    assert len(t.by_name("decode_step", "engine")) == s.decode_steps
    assert len(t.by_name("prefill", "request")) == s.prefills
    # finish args carry per-request token counts summing to the total
    toks = sum(e[6]["tokens"] for e in t.by_name("finish", "request"))
    assert toks == s.tokens_generated
    # the metrics plane saw the same population
    hists = m.app_histograms("serve")
    assert hists["repro_ttft_seconds"]["count"] == s.ttft_count
    assert hists["repro_queue_wait_seconds"]["count"] == s.admitted
    assert hists["repro_batch_occupancy"]["count"] == s.decode_steps
    # a null engine has no decode fn: nothing to time, so no decode
    # latency histogram may appear (absence IS the correct reading)
    assert "repro_decode_step_seconds" not in hists
    # every admit records a non-negative queue wait
    assert all(e[6]["queue_wait_s"] >= 0.0
               for e in t.by_name("admit", "request"))
    # the step's own spans: one admission pass a step, one grow and one
    # retire span a decode step, their args summing to the counters; a
    # null engine has no runner, so no runner spans
    passes = t.by_name("admit_pass", "engine")
    assert len(passes) >= s.decode_steps
    assert sum(e[6]["admitted"] for e in passes) == s.admitted
    assert len(t.by_name("grow", "engine")) == s.decode_steps
    retire = t.by_name("retire", "engine")
    assert len(retire) == s.decode_steps
    assert sum(e[6]["finished"] for e in retire) == s.completed
    assert not [e for e in t.snapshot() if e[3] == "runner"]


def test_pool_events_and_preempt():
    # pool arbitration events emit from the pod-shared PoolView (the
    # tenancy layer) -- a tiny quota forces denials and preemptions
    t = obs.enable()
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=NullExecutor(), pool_pages=8)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="obs-pool",
        serve=ServeOptions(max_batch=4)))
    for i in range(4):
        h.submit_request(Request(f"r{i}", PAGE_SIZE - 4, 3 * PAGE_SIZE))
    h.run(max_steps=50_000)
    eng = h.engine
    grants = t.by_name("grant", "pool")
    assert grants and all(e[6]["pages"] >= 1 for e in grants)
    assert all(e[5] == "obs-pool" for e in grants), "scope = the app"
    if eng.pool.stats["denials"]:
        denials = t.by_name("denial", "pool")
        assert denials and denials[0][6]["cause"] in ("quota", "physical")
    assert len(t.by_name("preempt", "request")) == eng.stats.preempted
    # every preemption happens in a grow pass, which counts it
    assert sum(e[6]["preempted"] for e in t.by_name("grow", "engine")) \
        == eng.stats.preempted
    h.release()


def test_park_unpark_and_autoscale_events():
    t = obs.enable()
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=NullExecutor(), pool_pages=32)
    cluster.enable_autoscale(idle_park_s=2.0, confirm_ticks=1)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="obs-park",
        serve=ServeOptions(max_batch=4)))
    # direct park with a request mid-flight: the drain must be visible
    h.submit_request(Request("r0", PAGE_SIZE - 4, 300))
    for _ in range(3):
        h.step()
    h.park()
    (park,) = t.by_name("park", "autoscale")
    assert park[5] == "obs-park" and park[6]["drained_requests"] == 1
    assert [e[5] for e in t.by_name("park", "request")] == ["r0"]
    h.unpark()
    (unpark,) = t.by_name("unpark", "autoscale")
    assert unpark[5] == "obs-park" and unpark[6]["restored_requests"] == 1
    (rup,) = t.by_name("unpark", "request")
    assert rup[5] == "r0" and rup[6]["restored"] is True
    # scheduler-plane receipts for the same episode
    assert t.by_name("job_park", "scheduler")
    assert t.by_name("job_unpark", "scheduler")
    h.run(max_steps=50_000)
    # controller-driven park after sustained idleness: the decision
    # event must explain itself (rule + the windowed rates it saw)
    tick = 0.0
    while not h.parked and tick < 20.0:
        cluster.tick(now=tick)
        tick += 1.0
    assert h.parked
    (dec,) = [e for e in t.by_name("decision", "autoscale")
              if e[6]["action"] == "park"]
    assert dec[5] == "obs-park" and "reason" in dec[6]
    assert any(k.startswith("rate_") for k in dec[6]), \
        "a decision must carry the windowed rates it saw"
    h.submit_request(Request("r1", 32, 4))   # transparent unpark
    assert not h.parked
    h.run(max_steps=50_000)
    h.release()
    assert t.by_name("job_finish", "scheduler")


# ---------------------------------------------------------------------------
# the serving step's own spans on the paged path
# ---------------------------------------------------------------------------

def _toks(n, seed):
    return tuple((seed * 7919 + i * 31) % 211 for i in range(n))


def _paged_app(prefix):
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=JaxExecutor(seed=0), pool_pages=64)
    return cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="obs-paged",
        serve=ServeOptions(max_batch=4, backend="paged", policy="fixed",
                           prefix_cache=prefix)))


def _serve_paged(h, n=6):
    """``n`` requests over one shared two-page prefix, through
    ``AppHandle.submit_request`` / ``AppHandle.step``; returns the number
    of steps."""
    for i in range(n):
        toks = _toks(2 * PAGE_SIZE + 10, 1) + _toks(40 + 9 * i, 100 + i)
        h.submit_request(Request(f"p{i}", len(toks), 5, prompt_tokens=toks))
    steps = 0
    while h.step()["alive"]:
        steps += 1
    return steps + 1


def _inside(child, parents):
    a, b = child[0], child[0] + child[1]
    return any(p[0] <= a and b <= p[0] + p[1] for p in parents)


@pytest.mark.parametrize("prefix", [False, True],
                         ids=["whole-prompt", "prefix-chunked"])
def test_paged_step_spans_match_engine_counters(prefix):
    h = _paged_app(prefix)
    t = obs.enable()
    steps = _serve_paged(h)
    s = h.engine.stats
    # the events that were there keep their counts
    assert len(t.by_name("submit", "request")) == 6
    assert len(t.by_name("admit", "request")) == s.admitted == 6
    assert len(t.by_name("finish", "request")) == s.completed == 6
    assert len(t.by_name("first_token", "request")) == s.ttft_count
    assert len(t.by_name("decode_step", "engine")) == s.decode_steps
    assert len(t.by_name("prefill", "request")) == s.prefills
    # one device wait per decode step and per prefill
    fetches = t.by_name("token_fetch", "runner")
    assert len(fetches) == s.decode_steps
    assert sum(e[6]["batch"] for e in fetches) == s.tokens_generated
    assert len(t.by_name("first_token_fetch", "runner")) == s.prefills
    assert len(t.by_name("decode_dispatch", "runner")) == s.decode_steps
    prompts = t.by_name("prompt_upload", "runner")
    assert len(prompts) == s.prefills
    assert all(e[6]["tokens"] % PAGE_SIZE == 0 for e in prompts)
    uploads = t.by_name("decode_inputs", "runner")
    assert len(uploads) == s.decode_steps
    assert [e[6]["batch"] for e in uploads] == [e[6]["batch"]
                                                 for e in fetches]
    assert all(e[6]["table_pages"] & (e[6]["table_pages"] - 1) == 0
               for e in uploads), "table widths are powers of two"
    # one engine/step span per AppHandle.step, with the pool's counters
    step_spans = t.by_name("step", "engine")
    assert len(step_spans) == steps
    assert all(e[5] == "obs-paged" and e[2] == "X" for e in step_spans)
    assert all(0 <= e[6]["pages_used"] <= e[6]["pool_pages"] == 64
               for e in step_spans)
    assert all(set(e[6]) == {"pages_used", "pool_pages"} for e in step_spans)
    # every span below the step lies inside one engine/step span
    below = [e for e in t.snapshot()
             if e[2] == "X" and (e[3] == "runner" or e[3] == "engine"
                                 and e[4] != "step")]
    assert below and all(_inside(e, step_spans) for e in below)
    # the engine's spans share its lane
    lanes = {e[5] for e in below if e[3] == "engine"}
    assert lanes == {t.by_name("decode_step", "engine")[0][5]}
    chunks = t.by_name("prefill_chunk", "request")
    pins = t.by_name("prefix_pin", "request")
    if prefix:
        # the instants that became spans keep their count and args
        assert len(pins) == len(t.by_name("prefix_lookup", "pool")) == 6
        assert sum(e[6]["pages"] for e in chunks) \
            == h.runner.prefill_pages_computed
        assert t.by_name("prefix_insert", "request")
        for e in chunks + pins + t.by_name("prefix_insert", "request"):
            assert e[2] == "X" and _inside(e, step_spans)
    else:
        assert not chunks and not pins
    h.release()


def test_disabled_plane_reads_no_clock_on_the_paged_path(monkeypatch):
    """With tracing off the new sites read no clock: the runner and the
    app handle read none, and the engine's step reads only the clock it
    always read (the first-token stamp, and the decode step's ends)."""
    h = _paged_app(True)
    _serve_paged(h, n=2)                   # compile every shape first
    reads = {}
    clock = time.perf_counter

    def counted():
        f = sys._getframe(1)
        key = (f.f_globals.get("__name__"), f.f_code.co_name)
        reads[key] = reads.get(key, 0) + 1
        return clock()

    engine_steps = []
    step = ServingEngine.step

    def counted_step(self):
        engine_steps.append(1)
        return step(self)

    monkeypatch.setattr(time, "perf_counter", counted)
    monkeypatch.setattr(ServingEngine, "step", counted_step)
    d0 = h.engine.stats.decode_steps
    _serve_paged(h, n=2)
    monkeypatch.undo()
    assert obs.current() is None
    decodes = h.engine.stats.decode_steps - d0
    assert decodes > 0
    mine = {k: v for k, v in reads.items()
            if k[0] in ("repro.serving.model_runner", "repro.runtime.cluster",
                        "repro.serving.engine")}
    assert mine == {("repro.serving.engine", "step"):
                    len(engine_steps) + 2 * decodes}
    h.release()


def test_anchor_marks_both_clocks_without_a_profiler():
    t = obs.enable()
    before = time.perf_counter()
    t.anchor()
    t.anchor()
    (a, b) = t.by_name("clock", "obs")
    for e in (a, b):
        assert e[2] == "i" and e[1] == 0.0 and e[5] is None
        assert before <= e[6]["t_in"] == e[0] <= e[6]["t_out"]
    assert a[6]["t_out"] <= b[6]["t_in"]


def test_gc_spans_while_enabled_only():
    obs.enable()
    t = obs.enable()                   # a second enable keeps one hook

    def hooks():
        return [cb for cb in gc.callbacks
                if isinstance(getattr(cb, "__self__", None), obs.Tracer)]

    assert hooks() == [t._on_gc]
    gc.collect()
    spans = t.by_name("gc", "host")
    assert spans and all(e[2] == "X" and e[1] >= 0.0 and e[6]["collected"]
                         >= 0 for e in spans)
    assert spans[-1][6]["generation"] == 2
    assert obs.disable() is t and hooks() == []
    gc.collect()
    assert t.by_name("gc", "host") == spans


# ---------------------------------------------------------------------------
# exporters + CLI
# ---------------------------------------------------------------------------

def _traced_run(tmp_path, fmt):
    t = obs.enable()
    eng, _ = _drive(n=4)
    path = str(tmp_path / f"trace.{fmt}")
    n = (obs.write_jsonl(t, path) if fmt == "jsonl"
         else obs.write_chrome_trace(t, path, extra_meta={"k": "v"}))
    return t, eng, path, n


@pytest.mark.parametrize("fmt", ["json", "jsonl"])
def test_export_roundtrip(tmp_path, fmt):
    t, eng, path, n = _traced_run(tmp_path, fmt)
    assert n == len(t)
    events = obs.load_events(path)
    assert len(events) == len(t), "loader must drop only metadata rows"
    reqs = request_lifecycles(events)
    assert len(reqs) == 4
    for r in reqs.values():
        assert r["submit"] is not None and r["finish"] is not None
        assert r["finish"] >= r["submit"] >= 0.0   # ts relative to t0
        assert r["ttft"] is not None and r["tokens"] == 8
    # durations survive in seconds through either format (a null engine
    # emits prefill as an instant -- dur 0 -- rather than a span)
    prefills = [e for e in events if e["name"] == "prefill"]
    assert len(prefills) == eng.stats.prefills
    assert all(0.0 <= e["dur"] < 60.0 for e in prefills)
    assert all(e["args"]["prompt_len"] == 48 for e in prefills)


def test_chrome_trace_shape(tmp_path):
    t, _, path, _ = _traced_run(tmp_path, "json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["dropped_events"] == 0
    assert doc["otherData"]["k"] == "v"
    evs = doc["traceEvents"]
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"request", "engine", "pool"} <= procs
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"r0", "r1", "r2", "r3"} <= lanes
    # every non-meta event has a resolvable pid/tid and us timestamps
    assert all(e["ts"] >= 0.0 for e in evs if e["ph"] != "M")


def test_cli_summary(tmp_path):
    _, eng, path, _ = _traced_run(tmp_path, "json")
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs", path],
        capture_output=True, text=True, check=True).stdout
    assert "== trace summary ==" in out
    assert f"decode steps: {eng.stats.decode_steps}" in out
    assert "p50=" in out and "p95=" in out and "p99=" in out
    assert "ttft" in out and "queue_wait" in out and "decode_step" in out
    assert "== slowest request" in out
    # the lifecycle table has one row per request
    assert all(f"r{i} " in out for i in range(4))


def test_summarize_handles_sparse_traces():
    assert "requests: 0" in summarize([])
    only_pool = [{"ts": 0.0, "dur": 0.0, "ph": "i", "cat": "pool",
                  "name": "grant", "scope": "a", "args": {"pages": 1}}]
    assert "pool=1" in summarize(only_pool)
    assert pctl([], 99) == 0.0 and pctl([3.0], 50) == 3.0


# ---------------------------------------------------------------------------
# windowed-stats edge cases (stats_delta / MetricsWindow satellites)
# ---------------------------------------------------------------------------

def _raw(admitted=4, **over):
    d = {"admitted": admitted, "completed": admitted, "rejected": 0,
         "preempted": 0, "decode_steps": admitted, "prefills": admitted,
         "tokens_generated": 2 * admitted, "ttft_s_sum": 0.0,
         "ttft_count": 0, "decode_s_sum": 0.0}
    d.update(over)
    return d


def test_stats_delta_missing_subdicts():
    # no pool/shared_pool/hist anywhere: plain counters still window
    d = stats_delta(_raw(6), _raw(2))
    assert d["admitted"] == 4 and "pool" not in d and "hist" not in d
    # since lacks (or corrupts) the sub-dicts cur carries
    cur = _raw(6, pool={"grants": 5, "denials": 2, "num_pages": 64},
               shared_pool={"cross_app_preemptions": 3,
                            "denials_by_app": {"a": 2}},
               hist={"h": {"bounds": [1.0], "counts": [2, 0],
                           "sum": 1.0, "count": 2}})
    for bad_since in (_raw(2),
                      _raw(2, pool=None, shared_pool=7, hist="nope")):
        d = stats_delta(cur, bad_since)
        assert d["pool"]["grants"] == 5 and d["pool"]["num_pages"] == 64
        assert d["shared_pool"]["cross_app_preemptions"] == 3
        assert d["shared_pool"]["denials_by_app"] == {"a": 2}
        assert d["hist"]["h"]["count"] == 2


def test_stats_delta_counter_reset_clamps():
    # a fresh engine under an old name: since > cur everywhere
    d = stats_delta(_raw(1, pool={"grants": 1, "denials": 0},
                         shared_pool={"cross_app_preemptions": 0,
                                      "denials_by_app": {"a": 0}}),
                    _raw(9, pool={"grants": 9, "denials": 4},
                         shared_pool={"cross_app_preemptions": 5,
                                      "denials_by_app": {"a": 7}}))
    assert d["admitted"] == 0 and d["pool"]["grants"] == 0
    assert d["shared_pool"]["cross_app_preemptions"] == 0
    assert d["shared_pool"]["denials_by_app"] == {"a": 0}


def test_stats_delta_zero_count_window_means():
    d = stats_delta(_raw(4), _raw(4))
    assert d["mean_ttft_s"] == 0.0 and d["mean_decode_step_s"] == 0.0


def test_metrics_window_zero_count_holds_ewma():
    w = MetricsWindow(alpha=1.0)
    w.observe(_raw(0), now=0.0)
    w.observe(_raw(4, ttft_s_sum=2.0, ttft_count=4), now=1.0)
    assert w.rates["ttft_s"] == pytest.approx(0.5)
    # an idle window (no ttft samples) must HOLD the smoothed value,
    # not decay it toward a fake 0.0
    w.observe(_raw(4, ttft_s_sum=2.0, ttft_count=4), now=2.0)
    assert w.rates["ttft_s"] == pytest.approx(0.5)
    assert w.idle_s == pytest.approx(1.0)


def test_serving_stats_hist_windows_through_since():
    obs.enable_metrics()
    cluster = Cluster(pods=1, executor=NullExecutor(), pool_pages=64)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="histwin",
        serve=ServeOptions(max_batch=4)))
    for i in range(3):
        h.submit_request(Request(f"r{i}", 16, 4))
    while h.step()["alive"]:
        pass
    mark = h.serving_stats()
    assert mark["hist"]["repro_ttft_seconds"]["count"] == 3
    assert mark["hist"]["repro_batch_occupancy"]["bounds"] == \
        list(OCCUPANCY_BOUNDS)
    for i in range(3, 5):
        h.submit_request(Request(f"r{i}", 16, 4))
    while h.step()["alive"]:
        pass
    win = h.serving_stats(since=mark)
    assert win["hist"]["repro_ttft_seconds"]["count"] == 2, \
        "histograms must window like every other counter"
    assert win["hist"]["repro_ttft_seconds"]["bounds"] == \
        list(LATENCY_BOUNDS)
    h.release()
