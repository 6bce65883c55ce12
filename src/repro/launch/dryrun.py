import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  1. materialize() produces the adaptive Plan (the paper's technique);
  2. the step function is lowered with the Plan's shardings and compiled;
  3. memory_analysis() proves per-chip fit -- if it exceeds the HBM budget
     the materializer ladder escalates and we recompile (the paper's
     reactive auto-scaling / runtime recompilation path);
  4. cost_analysis() + HLO collective parsing feed §Roofline;
  5. XLA counts scan bodies once, so the roofline FLOPs/bytes come from a
     two-point extrapolation: unrolled probes at num_blocks=1 and 2 give
     the exact per-block cost, then total = F1 + (NB-1)*(F2-F1).

Artifacts: artifacts/dryrun/{arch}__{shape}__{mesh}.json (resumable sweep).
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, list_archs, shape_applicable
from repro.core.history import HistoryStore
from repro.core.materializer import (MESHES, GB, Plan, escalate, materialize)
from repro.launch.input_specs import input_specs
from repro.launch.mesh import make_mesh_from_spec
from repro.models.model import Model
from repro.models.transformer import ImplConfig
from repro.sharding import planner
from repro.training import optimizer as opt
from repro.training.train_step import make_train_step
from repro.serving.serve_step import make_decode_step, make_prefill_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind op count and output bytes from optimized HLO."""
    stats: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (\([^)]*\)|\S+) ([\w\-]+)\(", s)
        if not m:
            continue
        opname = m.group(2)
        # normalize variants like all-reduce-start, all-gather-done
        base = None
        for k in COLLECTIVES:
            if opname == k or opname.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        if opname.endswith("-done"):
            continue  # avoid double counting start/done pairs
        stats[base]["count"] += 1
        stats[base]["bytes"] += _shape_bytes(m.group(1))
    return stats


def _merge_costs(c1: Dict, c2: Dict, nb: int) -> Dict[str, float]:
    """Two-point extrapolation: total = F1 + (nb - 1) * max(F2 - F1, 0).

    The per-block delta is clamped at zero: XLA occasionally CSEs a
    replicated collective at nb=2 that exists at nb=1, which would
    otherwise extrapolate to nonsense negative totals."""
    out = {}
    keys = set(c1) | set(c2)
    for k in keys:
        a, b = float(c1.get(k, 0.0)), float(c2.get(k, 0.0))
        out[k] = a + (nb - 1) * max(b - a, 0.0)
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _model_impl(plan: Plan, unroll: bool, nb_override: Optional[int],
                mesh=None, *, is_decode: bool = False) -> ImplConfig:
    shard_ctx = None
    if mesh is not None and is_decode and (plan.kv_shard_seq or plan.seq_axes):
        seq_axes = plan.seq_axes or ("model",)
        shard_ctx = (mesh, tuple(seq_axes), tuple(plan.batch_axes))
    ep_ctx = None
    if mesh is not None and plan.ep:
        ep_ctx = (mesh, "model", tuple(plan.batch_axes))
    return ImplConfig(attn_impl=plan.attn_impl,
                      remat=plan.remat if plan.shape == "train_4k" else "none",
                      scan_blocks=not unroll, unroll_blocks=unroll,
                      num_blocks_override=nb_override,
                      decode_shard_ctx=shard_ctx,
                      ep_shard_ctx=ep_ctx,
                      loss_chunk=plan.loss_chunk,
                      moe_dispatch=plan.moe_dispatch,
                      scan_chunk=plan.scan_chunk)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, plan: Plan, mesh,
               *, unroll: bool = False, nb_override: Optional[int] = None,
               donate: bool = True):
    """Build + lower the step for one cell under a plan.  Returns Lowered."""
    impl = _model_impl(plan, unroll, nb_override, mesh,
                       is_decode=shape.is_decode)
    model = Model(cfg, impl)
    specs = model.param_specs()
    pstructs = model.param_structs()
    p_sharding = planner.to_named(
        planner.param_specs_tree(plan, cfg, specs), mesh)
    ins = input_specs(cfg, shape)

    if shape.kind == "train":
        ostructs = opt.opt_state_structs(pstructs)
        o_sharding = {
            "m": planner.to_named(
                planner.opt_state_specs_tree(plan, cfg, specs), mesh),
            "v": planner.to_named(
                planner.opt_state_specs_tree(plan, cfg, specs), mesh),
            "master": planner.to_named(
                planner.opt_state_specs_tree(plan, cfg, specs), mesh),
            "count": NamedSharding(mesh, P()),
        }
        b_sharding = {
            k: NamedSharding(mesh, planner.batch_spec(plan, len(v.shape) - 1))
            for k, v in ins.items()}
        step = make_train_step(model, plan)
        jf = jax.jit(step,
                     in_shardings=(p_sharding, o_sharding, b_sharding),
                     out_shardings=(p_sharding, o_sharding, None),
                     donate_argnums=(0, 1) if donate else ())
        with mesh:
            return jf.lower(pstructs, ostructs, ins), model

    if shape.kind == "prefill":
        cache_structs = model.cache_specs(shape.global_batch, shape.seq_len)
        c_sharding = planner.to_named(
            planner.cache_specs_tree(plan, cfg, cache_structs), mesh)
        b_sharding = {
            k: NamedSharding(mesh, planner.batch_spec(plan, len(v.shape) - 1))
            for k, v in ins.items()}
        step = make_prefill_step(model, shape.seq_len)
        jf = jax.jit(step, in_shardings=(p_sharding, b_sharding),
                     out_shardings=(None, c_sharding))
        with mesh:
            return jf.lower(pstructs, ins), model

    # decode
    cache_structs = model.cache_specs(shape.global_batch, shape.seq_len)
    c_sharding = planner.to_named(
        planner.cache_specs_tree(plan, cfg, cache_structs), mesh)
    tok_sharding = NamedSharding(mesh, planner.batch_spec(plan, 1))
    pos_sharding = NamedSharding(mesh, P())
    step = make_decode_step(Model(cfg, impl))

    def decode(params, tokens, cache, pos):
        return step(params, tokens, cache, pos)

    jf = jax.jit(decode,
                 in_shardings=(p_sharding, tok_sharding, c_sharding,
                               pos_sharding),
                 out_shardings=(tok_sharding, None, c_sharding),
                 donate_argnums=(2,) if donate else ())
    with mesh:
        return jf.lower(pstructs, ins["tokens"], cache_structs, ins["pos"]), \
            Model(cfg, impl)


def cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())


def memory_footprint(compiled) -> Dict[str, int]:
    """Per-device footprint.  ``peak_tpu_adjusted`` halves the temp term:
    XLA:CPU has no native bf16, so it materializes fp32 shadow copies of
    every bf16 tensor feeding a dot (verified in buffer-assignment dumps:
    the dominant temps are f32[...] shadows of bf16 weights/caches, exactly
    2x).  On the TPU target those conversions do not exist; halving the
    CPU temp is the documented, uniformly-applied correction."""
    ma = compiled.memory_analysis()
    state = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes)
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        "peak_bytes": state + int(ma.temp_size_in_bytes),
        "peak_tpu_adjusted": state + int(ma.temp_size_in_bytes) // 2,
    }


# ---------------------------------------------------------------------------
# Full cell run: compile + feedback + cost probes + roofline terms
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             history: Optional[HistoryStore] = None,
             overrides: Optional[Dict] = None,
             max_escalations: int = 6,
             cost_probes: bool = True,
             keep_hlo: bool = False) -> Dict[str, Any]:
    # the cell is an Application invocation class: resolve config/shape and
    # the proactive resource profile through the runtime's description
    from repro.runtime import Application

    shape = SHAPES[shape_name]
    app = (Application.train(arch, shape=shape) if shape.kind == "train"
           else Application.serve(arch, shape=shape))
    cfg = app.config
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}
    graph = app.resource_graph()
    mesh_spec = MESHES[mesh_name]
    mesh = make_mesh_from_spec(mesh_spec)
    plan = materialize(cfg, shape, mesh_spec, history=history,
                       overrides=overrides)
    budget = int(mesh_spec.hbm_per_device * 0.92)

    t0 = time.time()
    result: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                              "mesh": mesh_name}
    lowered = compiled = None
    for attempt in range(max_escalations + 1):
        lowered, _ = lower_cell(cfg, shape, plan, mesh)
        compiled = lowered.compile()
        mem = memory_footprint(compiled)
        if mem["peak_tpu_adjusted"] <= budget:
            break
        nxt = escalate(plan, cfg, shape, mem["peak_tpu_adjusted"])
        if nxt is None:
            plan.log("escalation exhausted; reporting over-budget compile")
            break
        plan = nxt
        jax.clear_caches()
    assert compiled is not None

    mem = memory_footprint(compiled)
    cost = cost_dict(compiled)
    hlo = compiled.as_text()
    colls = collective_stats(hlo)
    result.update({
        "status": "ok",
        "plan": plan.describe(),
        "resource_graph": {"compute": len(graph.compute),
                           "data": len(graph.data),
                           "estimated_demand_bytes": app.estimate_demand()},
        "memory": mem,
        "fits": mem["peak_tpu_adjusted"] <= budget,
        "hbm_budget": budget,
        "cost_scanned": {k: float(v) for k, v in cost.items()
                         if isinstance(v, (int, float))},
        "collectives_scanned": colls,
        "lower_compile_s": round(time.time() - t0, 2),
        "hlo_bytes": len(hlo),
    })
    if keep_hlo:
        result["hlo_head"] = hlo[:20000]

    # ---- two-point cost extrapolation (exact per-block costs) ------------
    if cost_probes:
        try:
            # probes lower one full-batch step without the microbatch
            # loop: total FLOPs are identical (mb x per-microbatch), and
            # nothing is executed so memory is irrelevant.
            probe_shape = shape
            probe_plan = dataclasses.replace(plan, microbatch=1)
            probe_plan.notes = []
            costs, coll_list = [], []
            for nb in (1, 2):
                l, _ = lower_cell(cfg, probe_shape, probe_plan, mesh,
                                  unroll=True, nb_override=nb, donate=False)
                c = l.compile()
                costs.append({k: float(v) for k, v in cost_dict(c).items()
                              if isinstance(v, (int, float))})
                coll_list.append(collective_stats(c.as_text()))
                del l, c
                jax.clear_caches()
            nb_total = cfg.num_blocks
            extr = _merge_costs(costs[0], costs[1], nb_total)
            coll_extr = {
                k: _merge_costs(coll_list[0][k], coll_list[1][k], nb_total)
                for k in COLLECTIVES}
            result["cost_extrapolated"] = extr
            result["collectives_extrapolated"] = coll_extr
            result["cost_probe_points"] = costs
        except Exception as e:  # pragma: no cover - probe robustness
            result["cost_probe_error"] = f"{type(e).__name__}: {e}"

    # ---- roofline terms ---------------------------------------------------
    result["roofline"] = roofline_terms(result, cfg, shape, mesh_spec)

    if history is not None:
        history.observe(arch, f"{shape_name}/{mesh_name}", "bytes_per_device",
                        mem["peak_bytes"])
        history.observe(arch, f"{shape_name}/{mesh_name}", "hlo_flops",
                        result["roofline"]["hlo_flops_per_device"])
        history.save()
    jax.clear_caches()
    return result


def roofline_terms(result: Dict, cfg: ModelConfig, shape: ShapeConfig,
                   mesh_spec) -> Dict[str, Any]:
    from repro.core import profiles as prof
    cost = result.get("cost_extrapolated") or result.get("cost_scanned", {})
    colls = (result.get("collectives_extrapolated")
             or result.get("collectives_scanned", {}))
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_bytes_dev = sum(d.get("bytes", 0.0) for d in colls.values())
    n_dev = mesh_spec.num_devices
    compute_s = flops_dev / mesh_spec.peak_flops
    memory_s = bytes_dev / mesh_spec.hbm_bw
    collective_s = coll_bytes_dev / mesh_spec.ici_bw
    model_flops = prof.step_model_flops(cfg, shape)
    hlo_flops_global = flops_dev * n_dev
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return {
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes_dev,
        "compute_term_s": compute_s,
        "memory_term_s": memory_s,
        "collective_term_s": collective_s,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else 0.0),
        "step_time_bound_s": max(compute_s, memory_s, collective_s),
        "mfu_upper_bound": (model_flops
                            / (max(compute_s, memory_s, collective_s)
                               * n_dev * mesh_spec.peak_flops)
                            if max(compute_s, memory_s, collective_s) > 0
                            else 0.0),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None,
                    choices=[None, "single_pod", "multi_pod"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", default=None,
                    help="JSON dict of Plan overrides (perf experiments)")
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(ARTIFACT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    history = HistoryStore(os.path.join(os.path.dirname(out_dir), "history"))

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single_pod", "multi_pod"]
    overrides = json.loads(args.override) if args.override else None

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_name in meshes:
                tag = f"{arch}__{shape_name}__{mesh_name}"
                path = os.path.join(out_dir, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {tag}")
                    continue
                print(f"[run] {tag}", flush=True)
                try:
                    res = run_cell(arch, shape_name, mesh_name,
                                   history=history, overrides=overrides,
                                   cost_probes=not args.no_probes)
                except Exception as e:
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=str)
                st = res.get("status")
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "error"
                if st == "ok":
                    r = res["roofline"]
                    print(f"  fits={res['fits']} "
                          f"peak={res['memory']['peak_tpu_adjusted']/GB:.2f}GiB(adj) "
                          f"dom={r['dominant']} "
                          f"mfu_ub={r['mfu_upper_bound']:.3f} "
                          f"t={res['lower_compile_s']}s", flush=True)
                elif st == "error":
                    print(f"  ERROR {res['error']}", flush=True)
                else:
                    print(f"  skipped: {res['reason']}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} failed={n_fail}")


if __name__ == "__main__":
    main()
