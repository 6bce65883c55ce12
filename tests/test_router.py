"""Scale-out data plane: RequestRouter + ReplicaSet.

Dispatch spreading, per-app fairness, token-identical replica drain
(and the dense at-least-once fallback), scale-to-zero == park, the
replica/batch autoscale dimensions, and the aggregated StatsView.
"""

import pytest

from repro import obs
from repro.core.history import HistoryStore
from repro.runtime import (Application, Cluster, JaxExecutor, NullExecutor,
                           ScalePolicy, ServeOptions)
from repro.serving.kv_cache import PAGE_SIZE, Request
from repro.serving.stats import aggregate_engine_stats


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.disable_metrics()
    yield
    obs.disable()
    obs.disable_metrics()


def _null_cluster(pool_pages=64):
    return Cluster(pods=1, history=HistoryStore(),
                   executor=NullExecutor(), pool_pages=pool_pages)


def _serve(cluster, name, **opts):
    return cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name=name,
        serve=ServeOptions(**opts)))


def _reqs(n, prefix="r", prompt=PAGE_SIZE - 4, max_new=6):
    return [Request(f"{prefix}{i}", prompt, max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def test_router_spreads_requests_across_replicas():
    cluster = _null_cluster()
    h = _serve(cluster, "spread", max_batch=2, replicas=3)
    for r in _reqs(12):
        h.submit_request(r)
    h.run(max_steps=1000)

    rset = h.replica_set
    assert len(rset.replicas) == 3
    # JSQ with batch headroom: nobody sits idle while others overflow
    per_replica = [r.engine.stats.admitted for r in rset.replicas]
    assert all(a > 0 for a in per_replica), per_replica
    rstats = h.serving_stats()["router"]
    assert rstats["submitted"] == 12
    assert rstats["dispatched"] == 12
    assert rstats["queue_len"] == 0
    assert aggregate_engine_stats(h).completed == 12
    h.release()


def test_router_late_binding_queues_when_full():
    """A request with no replica headroom waits at the ROUTER (where its
    depth is the scaling signal), not pinned early to an engine lane."""
    cluster = _null_cluster()
    h = _serve(cluster, "late", max_batch=2, replicas=2)
    for r in _reqs(9):
        h.submit_request(r)
    rstats = h.serving_stats()["router"]
    assert rstats["dispatched"] == 4          # 2 replicas x max_batch 2
    assert rstats["queue_len"] == 5
    h.run(max_steps=1000)
    assert aggregate_engine_stats(h).completed == 9
    h.release()


def test_router_fairness_no_starvation():
    """One heavy and one light tenant on the same pod router: the light
    tenant's requests complete in near-isolation latency because every
    app has its own queue + replicas (per-round service, no
    head-of-line blocking)."""
    cluster = _null_cluster(pool_pages=128)
    heavy = _serve(cluster, "heavy", max_batch=2)
    light = _serve(cluster, "light", max_batch=2)
    for r in _reqs(40, prefix="h"):
        heavy.submit_request(r)
    for r in _reqs(2, prefix="l"):
        light.submit_request(r)

    router = cluster.router(heavy.pod)
    assert router is cluster.router(light.pod)
    rounds = 0
    while light.engine.stats.completed < 2:
        assert router.step(), "router went idle with light reqs pending"
        rounds += 1
        assert rounds <= 25, "light tenant starved behind heavy backlog"
    # the heavy backlog is still mostly unserved: light did NOT wait on it
    assert heavy.engine.stats.completed < 40
    while router.step():
        pass
    assert heavy.engine.stats.completed == 40
    heavy.release()
    light.release()


# ---------------------------------------------------------------------------
# replica drain / failover
# ---------------------------------------------------------------------------

def _paged_tokens(replicas, drain_after=None):
    """Serve 4 requests on the paged backend; optionally drain one
    replica mid-decode.  Returns ({req_id: tokens}, receipt)."""
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=JaxExecutor(seed=0), pool_pages=96)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="drain",
        serve=ServeOptions(backend="paged", max_batch=2, replicas=replicas,
                           pool_pages=96, cache_len=512)))
    reqs = [Request(f"r{i}", 40 + 7 * i, max_new_tokens=8) for i in range(4)]
    for r in reqs:
        h.submit_request(r)
    receipt = None
    if drain_after is not None:
        for _ in range(drain_after):
            h.step()
        receipt = h.remove_replica()
    h.run(max_steps=500)
    toks = {r.req_id: list(r.output_tokens) for r in reqs}
    h.release()
    return toks, receipt


def test_replica_drain_token_identical_migration():
    """Mid-decode scale-in migrates in-flight requests to a survivor and
    the continuation is token-identical: replicas decode through one
    shared physical KV array set, so drained KV re-grants in place."""
    ref, _ = _paged_tokens(replicas=1)
    got, receipt = _paged_tokens(replicas=3, drain_after=3)
    assert receipt["migrated_requests"] >= 1, receipt
    assert all(len(t) > 8 for t in got.values())   # prefill token + decode
    assert got == ref


def test_dense_drain_falls_back_to_requeue():
    """The dense backend has no migratable page identity: scale-in
    requeues the victim's work at the router front (at-least-once,
    deterministic re-execution) instead of moving KV."""
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=JaxExecutor(seed=0), pool_pages=64)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="dense-drain",
        serve=ServeOptions(backend="dense", max_batch=2, replicas=2)))
    reqs = [Request(f"d{i}", 16 + 5 * i, max_new_tokens=4) for i in range(3)]
    for r in reqs:
        h.submit_request(r)
    for _ in range(2):
        h.step()
    receipt = h.remove_replica()
    assert receipt["migrated_requests"] == 0
    assert receipt["requeued_requests"] >= 1
    h.run(max_steps=500)
    assert aggregate_engine_stats(h).completed == 3
    assert all(len(r.output_tokens) > 4 for r in reqs)
    h.release()


def test_remove_last_replica_is_refused():
    cluster = _null_cluster()
    h = _serve(cluster, "last", max_batch=2)
    with pytest.raises(RuntimeError, match="park"):
        h.remove_replica()
    h.release()


# ---------------------------------------------------------------------------
# scale-to-zero == park
# ---------------------------------------------------------------------------

def test_scale_to_zero_is_park_round_trip():
    cluster = _null_cluster()
    h = _serve(cluster, "zero", max_batch=2, replicas=2,
               scale=ScalePolicy(min_replicas=0, max_replicas=3))
    for r in _reqs(4, prefix="a"):
        h.submit_request(r)
    h.run(max_steps=1000)
    assert h.num_replicas == 2

    receipt = h.park()
    assert h.parked and h.num_replicas == 0
    # park first scaled the set to one replica (nothing in flight here,
    # so nothing to migrate), then drained it
    assert receipt["migrated_requests"] == 0
    assert len(h.replica_set.replicas) == 1

    # demand-driven restart: submit lands on a live engine again
    for r in _reqs(2, prefix="b"):
        h.submit_request(r)
    assert not h.parked and h.num_replicas == 1
    h.add_replica()
    h.run(max_steps=1000)
    # retired-replica counters folded in: totals stay monotonic
    assert aggregate_engine_stats(h).completed == 6
    h.release()


# ---------------------------------------------------------------------------
# autoscaled replica count / batch width
# ---------------------------------------------------------------------------

def test_autoscaler_adds_replicas_on_queue_depth():
    tracer = obs.enable()
    cluster = _null_cluster()
    h = _serve(cluster, "scaleout", max_batch=2,
               scale=ScalePolicy(max_replicas=3,
                                 target_queue_per_replica=1.0))
    ctl = cluster.enable_autoscale(confirm_ticks=1, idle_park_s=1e9)
    for r in _reqs(8):
        h.submit_request(r)
    cluster.tick(now=0.0)
    cluster.tick(now=1.0)

    actions = [a["action"] for a in ctl.log]
    assert "add_replica" in actions, actions
    assert h.num_replicas >= 2
    # acceptance: scale decisions land in the trace WITH windowed rates
    decisions = tracer.by_name("decision", "autoscale")
    assert decisions
    assert any(k.startswith("rate_") for k in decisions[0][6])
    assert tracer.by_name("replica_add", "autoscale")
    h.run(max_steps=1000)
    assert aggregate_engine_stats(h).completed == 8
    h.release()


def test_autoscaler_widens_batch_on_occupancy():
    cluster = _null_cluster()
    h = _serve(cluster, "widen", max_batch=2,
               scale=ScalePolicy(batch_max=8))
    ctl = cluster.enable_autoscale(confirm_ticks=1, idle_park_s=1e9)
    for r in _reqs(6):
        h.submit_request(r)
    h.step()                    # both slots busy: occupancy 1.0, queue > 0
    cluster.tick(now=0.0)       # baseline observation
    cluster.tick(now=1.0)
    grown = [a for a in ctl.log if a["action"] == "grow_batch"]
    assert grown, ctl.log
    assert h.replica_set.max_batch == 4      # doubled, inside batch_max
    h.run(max_steps=1000)
    assert aggregate_engine_stats(h).completed == 6
    h.release()


def test_predictive_unpark_wakes_before_forecast_arrival():
    """A periodic tenant parked between bursts is warm-restarted
    ``unpark_lead_s`` ahead of the EWMA-forecast next arrival."""
    cluster = _null_cluster()
    h = _serve(cluster, "periodic", max_batch=2,
               scale=ScalePolicy(min_replicas=0, max_replicas=1))
    ctl = cluster.enable_autoscale(confirm_ticks=1, idle_park_s=1e9)
    for i, t in enumerate((0.0, 10.0, 20.0)):   # arrivals every 10s
        h.submit_request(Request(f"p{i}", PAGE_SIZE - 4, 4))
        h.run(max_steps=200)
        cluster.tick(now=t)
    h.park()
    assert h.parked

    cluster.tick(now=25.0)                      # well before the forecast
    assert h.parked
    cluster.tick(now=29.5)                      # 29.5 + lead 1.0 >= due 30.0
    assert not h.parked
    assert "unpark" in [a["action"] for a in ctl.log]
    h.release()


# ---------------------------------------------------------------------------
# stats surface
# ---------------------------------------------------------------------------

def test_stats_view_aggregates_replicas():
    cluster = _null_cluster()
    h = _serve(cluster, "sv", max_batch=2, replicas=3)
    view = h.stats_view
    mark = view.mark()
    for r in _reqs(9):
        h.submit_request(r)
    h.run(max_steps=1000)

    cum = view.cumulative()
    assert cum["completed"] == 9
    names = [rep["view"] for rep in cum["replicas"]]
    assert names == ["sv", "sv@r1", "sv@r2"]
    assert sum(rep["completed"] for rep in cum["replicas"]) == 9
    assert cum["router"]["dispatched"] == 9

    win = view.windowed(mark)
    assert win["completed"] == 9
    assert win["router"]["submitted"] == 9
    # a windowed result is not a marker
    with pytest.raises(ValueError, match="RAW snapshot"):
        view.windowed(win)

    # scale-down retires an engine; aggregated totals stay monotonic
    h.remove_replica()
    assert view.cumulative()["completed"] == 9
    h.release()


@pytest.mark.parametrize("backend", ["paged", "dense"])
def test_replicas_share_the_primary_weights(backend, monkeypatch):
    """A replica of the same model reuses the primary's weight tree; it
    never initializes a second copy first (on a chip that transient copy
    is a second full set of weights plus its f32 temporaries)."""
    from repro.models.model import Model
    inits = []
    real = Model.init_params
    monkeypatch.setattr(Model, "init_params",
                        lambda self, rng: inits.append(1) or real(self, rng))
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=JaxExecutor(seed=0), pool_pages=64)
    h = _serve(cluster, f"weights-{backend}", backend=backend, max_batch=2,
               replicas=2)
    h.add_replica()
    runners = [r.runner for r in h.replica_set.replicas]
    assert len(runners) == 3 and len(inits) == 1
    assert all(r.params is runners[0].params for r in runners)
    h.release()


def test_park_frees_retired_replica_weights_without_gc():
    """Parking a two-replica app releases its weights at once: the replica
    folded away before the drain drops its reference instead of keeping
    the tree alive in a reference cycle until the cyclic collector runs
    (on the chip, unpark then re-uploaded beside the stale copy)."""
    import gc
    import weakref

    import jax
    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=JaxExecutor(seed=0), pool_pages=64)
    h = _serve(cluster, "park-free", backend="paged", max_batch=2,
               replicas=2)
    for r in _reqs(4):
        h.submit_request(r)
    h.step()
    h.step()
    refs = [weakref.ref(x) for x in jax.tree.leaves(h.runner.params)]
    gc.collect()
    gc.disable()
    try:
        h.park()
        alive = sum(r() is not None for r in refs)
    finally:
        gc.enable()
    assert alive == 0, f"{alive} weight arrays outlived park"
    h.submit_request(Request("late", PAGE_SIZE - 4, 2))     # unparks
    assert h.run(max_steps=1000)["completed"] == 5
    h.release()
