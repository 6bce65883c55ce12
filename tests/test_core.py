"""Unit tests for the paper-core: materializer ladder, planner, resource
graph, history, scheduler, compile cache."""

import dataclasses
import os

import jax
import pytest

from repro.configs import ALL_ARCHS, SHAPES, get_config, shape_applicable
from repro.core.graph import build_resource_graph
from repro.core.history import DecayedHistogram, HistoryStore
from repro.core.materializer import (CHIPS, MESHES, MULTI_POD, SINGLE_POD,
                                     GB, MeshSpec, chip_spec,
                                     estimate_bytes_per_device, escalate,
                                     materialize)
from repro.core.compile_cache import CompileCache, plan_layout_key
from repro.core.scheduler import GlobalScheduler, Job, PodState
from repro.sharding import planner
from repro.models.transformer import model_specs
from repro.models import layers as L


# ---------------------------------------------------------------------------
# materializer
# ---------------------------------------------------------------------------

def test_chip_table_is_the_only_source_of_figures():
    """Production meshes take their figures from the chip table; a kind
    the table does not list raises instead of getting a default."""
    v5e = CHIPS["TPU v5 lite"]
    for mesh in (SINGLE_POD, MULTI_POD):
        assert (mesh.hbm_per_device, mesh.peak_flops, mesh.hbm_bw,
                mesh.ici_bw) == (v5e.hbm_bytes, v5e.peak_flops, v5e.hbm_bw,
                                 v5e.ici_bw)
    with pytest.raises(ValueError, match="cpu"):
        chip_spec("cpu")
    with pytest.raises(TypeError):
        MeshSpec("bare", (1, 1), ("data", "model"))     # no silent figures
    one = MeshSpec.of_chip("one", (1, 1), ("data", "model"), "TPU v5 lite",
                           hbm_per_device=15 * GB)
    assert one.num_devices == 1 and one.hbm_per_device == 15 * GB
    assert one.peak_flops == v5e.peak_flops


@pytest.mark.parametrize("mesh", ["single_pod", "multi_pod"])
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_materialize_all_cells(arch, mesh):
    cfg = get_config(arch)
    for sname, shape in SHAPES.items():
        ok, _ = shape_applicable(cfg, shape)
        if not ok:
            continue
        plan = materialize(cfg, shape, MESHES[mesh])
        # batch axes must divide the global batch
        deg = plan.dp_degree
        assert shape.global_batch % max(deg, 1) == 0, (arch, sname)
        # microbatch respects DP divisibility
        if shape.kind == "train":
            assert (shape.global_batch // max(deg, 1)) % plan.microbatch == 0
        # MoE archs get EP whenever TP is on
        if cfg.moe is not None and plan.tp:
            assert plan.ep
        # decode shapes pick exactly one KV sharding strategy
        if shape.is_decode:
            assert plan.kv_shard_heads != plan.kv_shard_seq
            if cfg.num_kv_heads % 16 == 0:
                assert plan.kv_shard_heads
        assert plan.notes, "plan must carry an audit trail"


def test_ladder_escalates_under_pressure():
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    plan = materialize(cfg, shape, SINGLE_POD)
    # a 132B train job cannot be all-local: ladder must have escalated
    assert plan.tp and plan.fsdp and plan.zero
    assert plan.remat in ("dots", "full")


def test_all_local_for_small_model():
    cfg = get_config("tinyllama-1.1b")
    plan = materialize(cfg, SHAPES["train_4k"], SINGLE_POD)
    assert not plan.tp, "1.1B train should materialize all-local (pure DP)"
    assert plan.dp_degree == 256


def test_estimate_monotone_in_ladder():
    cfg = get_config("command-r-35b")
    shape = SHAPES["train_4k"]
    base = materialize(cfg, shape, SINGLE_POD,
                       overrides={"remat": "none", "microbatch": 1,
                                  "fsdp": False, "zero": False})
    est0 = estimate_bytes_per_device(cfg, shape, base)
    for kw in ({"zero": True}, {"remat": "full"}, {"fsdp": True},
               {"microbatch": 4}):
        nxt = dataclasses.replace(base, **kw)
        assert estimate_bytes_per_device(cfg, shape, nxt) <= est0, kw


def test_escalate_chain_terminates():
    cfg = get_config("mistral-nemo-12b")
    shape = SHAPES["train_4k"]
    plan = materialize(cfg, shape, SINGLE_POD)
    seen = set()
    for _ in range(24):
        key = (plan.remat, plan.microbatch, plan.fsdp, plan.zero,
               plan.attn_impl, plan.tp, plan.offload_optimizer,
               plan.fsdp_contracting, plan.loss_chunk)
        assert key not in seen, "escalation revisited a state"
        seen.add(key)
        nxt = escalate(plan, cfg, shape, measured_bytes=1 << 60)
        if nxt is None:
            break
        plan = nxt
    else:
        pytest.fail("escalation did not terminate")


def test_long_context_seq_axes():
    cfg = get_config("gemma3-12b")
    plan = materialize(cfg, SHAPES["long_500k"], MULTI_POD)
    assert plan.batch_axes == ()          # batch 1 cannot shard
    assert plan.seq_axes, "long-context decode must shard the sequence"


# ---------------------------------------------------------------------------
# sharding planner
# ---------------------------------------------------------------------------

def _axes_size(mesh_spec, axes):
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh_spec.axis_size(a)
    return n


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    plan = materialize(cfg, SHAPES["train_4k"], SINGLE_POD)
    specs = model_specs(cfg)
    ptree = planner.param_specs_tree(plan, cfg, specs)
    flat_specs = jax.tree.leaves(specs, is_leaf=L.is_spec)
    flat_parts = jax.tree.leaves(
        ptree, is_leaf=lambda x: isinstance(x, planner.P))
    assert len(flat_specs) == len(flat_parts)
    for s, p in zip(flat_specs, flat_parts):
        for dim, entry in enumerate(p):
            if entry is None:
                continue
            assert s.shape[dim] % _axes_size(plan.mesh, entry) == 0, (
                arch, s.shape, p)


import jax  # noqa: E402  (after use above in tree ops)


def test_kv_heads_not_sharded_when_indivisible():
    cfg = get_config("mistral-nemo-12b")   # kv=8 vs model=16
    plan = materialize(cfg, SHAPES["train_4k"], SINGLE_POD)
    specs = model_specs(cfg)
    ptree = planner.param_specs_tree(plan, cfg, specs)
    wk = ptree["blocks"]["p0_attn_global"]["attn"]["wk"]
    assert "model" not in jax.tree.leaves(wk, is_leaf=lambda x: True)[0][2:3]


# ---------------------------------------------------------------------------
# resource graph
# ---------------------------------------------------------------------------

def test_graph_structure_dense():
    cfg = get_config("mistral-nemo-12b")
    g = build_resource_graph(cfg, SHAPES["train_4k"])
    order = g.topo_order()
    assert order[0] == "embed" and order[-1] == "optimizer"
    assert g.total_flops() > 0
    assert "optimizer" in g.cut_boundaries() or "head" in g.cut_boundaries()


def test_graph_shared_data_zamba():
    cfg = get_config("zamba2-2.7b")
    g = build_resource_graph(cfg, SHAPES["train_4k"])
    assert "w_shared_attn" in g.data


def test_graph_moe_dispatch_component():
    cfg = get_config("dbrx-132b")
    g = build_resource_graph(cfg, SHAPES["train_4k"])
    disp = [d for d in g.data.values() if d.input_dependent
            and d.lifetime == "transient"]
    assert disp, "MoE dispatch buffer must be an input-dependent component"


def test_graph_decode_kv_component():
    cfg = get_config("mistral-nemo-12b")
    g = build_resource_graph(cfg, SHAPES["decode_32k"])
    assert g.data["kv_cache"].bytes > 0
    assert len(g.accessors("kv_cache")) >= 1


# ---------------------------------------------------------------------------
# history
# ---------------------------------------------------------------------------

def test_decayed_histogram_quantiles():
    h = DecayedHistogram()
    for v in [10, 20, 30, 40, 1000]:
        h.observe(v)
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    assert h.peak() >= 500


def test_history_decay_forgets():
    h = DecayedHistogram(decay=0.5)
    h.observe(1000.0)
    for _ in range(20):
        h.observe(10.0)
    assert h.quantile(0.9) < 100


def test_history_store_persistence(tmp_path):
    st = HistoryStore(str(tmp_path))
    st.observe("app", "comp", "bytes", 123456)
    st.save()
    st2 = HistoryStore(str(tmp_path))
    assert st2.peak("app", "comp", "bytes") > 0


# ---------------------------------------------------------------------------
# two-level scheduler
# ---------------------------------------------------------------------------

def test_scheduler_best_fit_smallest():
    pods = [PodState("a", 256, 16 * GB), PodState("b", 128, 16 * GB)]
    sched = GlobalScheduler(pods)
    job = Job("j1", "app", "train", 100 * GB, 64)
    pod = sched.submit(job)
    assert pod == "b", "must pick the smallest sufficient pod"


def test_scheduler_queues_and_drains():
    pods = [PodState("a", 4, 16 * GB)]
    sched = GlobalScheduler(pods)
    j1 = Job("j1", "app", "train", 60 * GB, 4)
    j2 = Job("j2", "app", "train", 60 * GB, 4)
    assert sched.submit(j1) == "a"
    assert sched.submit(j2) is None        # queued
    assert len(sched.pending) == 1
    sched.finish(j1)
    assert j2.pod == "a" and not sched.pending


# (scheduler throughput is asserted in tests/test_runtime.py via the
# runtime's replay_trace -- the single simulation path after PR 1)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_single_flight_and_hits():
    cc = CompileCache()
    calls = []

    def build():
        calls.append(1)
        return "exe"

    assert cc.get_or_compile("k1", build) == "exe"
    assert cc.get_or_compile("k1", build) == "exe"
    assert len(calls) == 1
    assert cc.stats["hits"] == 1


def test_compile_cache_failed_build_releases_waiters():
    """A build that raises passes its error on, and a waiter on the same
    key is released (it retries as the new owner) instead of blocking."""
    import threading

    cc = CompileCache()
    started, release = threading.Event(), threading.Event()

    def failing():
        started.set()
        release.wait(5)
        raise RuntimeError("compile failed")

    errors, results = [], []

    def owner():
        try:
            cc.get_or_compile("k", failing)
        except RuntimeError as e:
            errors.append(str(e))

    t_owner = threading.Thread(target=owner)
    t_owner.start()
    assert started.wait(5)
    t_wait = threading.Thread(
        target=lambda: results.append(cc.get_or_compile("k", lambda: "exe")))
    t_wait.start()
    release.set()
    t_owner.join(5)
    t_wait.join(5)
    assert not t_owner.is_alive() and not t_wait.is_alive()
    assert errors == ["compile failed"]
    assert results == ["exe"] and cc.contains("k")


def test_compile_cache_prewarm_reports_failure():
    cc = CompileCache()

    def failing():
        raise ValueError("bad layout")

    with pytest.raises(ValueError, match="bad layout"):
        cc.prewarm("k", failing).result(timeout=5)
    assert cc.prewarm("k", lambda: "exe").result(timeout=5) == "exe"
    assert cc.stats["prewarmed"] == 1


def test_persistent_cache_placement(monkeypatch):
    """The environment variable wins and nothing is set in code; unset,
    the cache goes to the fixed git-ignored directory of the checkout."""
    from repro.core import compile_cache as ccm
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert ccm.configure_persistent_cache() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = ccm.configure_persistent_cache()
    assert updates == [("jax_compilation_cache_dir", path)]
    assert path == ccm.REPO_CACHE_DIR
    assert os.path.basename(path) == ".jax_cache"
    assert os.path.dirname(path) == os.path.abspath(
        os.path.join(os.path.dirname(__file__), ".."))


def test_plan_layout_key_stable():
    cfg = get_config("tinyllama-1.1b")
    p1 = materialize(cfg, SHAPES["train_4k"], SINGLE_POD)
    p2 = materialize(cfg, SHAPES["train_4k"], SINGLE_POD)
    assert plan_layout_key("a", "s", "m", p1) == plan_layout_key("a", "s", "m", p2)
    p3 = dataclasses.replace(p2, microbatch=p2.microbatch * 2)
    p3.notes = []
    assert plan_layout_key("a", "s", "m", p2) != plan_layout_key("a", "s", "m", p3)
