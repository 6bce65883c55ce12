"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell
asks for.  The cell, its configuration, its traffic mix and its metrics
are found by name (``BENCHMARK.json``, ``configs/``, ``traffic/``,
``metrics/``).  A run has three parts:

1. Set-up: the configuration's ``model_type`` picks the architecture
   module (``arch/<model_type>.py``) that configures the program, holds
   the plain reference and counts the work; the program draws its
   weights on the device from the seed;
   throwaway requests, served through the same public path, take every
   program shape and prompt operation the traffic can reach, compiled or
   loaded from JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR``
   where it is set, else a fixed directory of the checkout); and the
   prefix cache is filled as the traffic would fill it.
2. The window: the traffic is offered for ``--seconds`` through
   ``AppHandle.submit_request`` / ``AppHandle.step``.  With ``--trace 1``
   the repository's request tracer is on and the profiler traces the
   window's last stretch.
3. The check and the result: a sample of the served requests is compared
   with the architecture's plain float32 reference after the
   program's state is freed.  The last line of standard output is one
   JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
   cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device`` and ``checks`` (each compared number beside its limit),
   with ``--trace 1`` also ``breakdown``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--control 1`` (never used by the
benchmark's own runs) puts the float8 control in the program's place in
the comparison, on the same sample: such a run has to come out not
correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: where a traced run's profile goes (emptied before each traced run)
TRACE_DIR = os.path.join(HERE, "out", "trace")
#: the profiler traces this much of the window's end, at most
TRACE_SECONDS = 8.0
#: served tokens the correctness sample aims at, and its most requests
SAMPLE_TOKENS = 384
SAMPLE_REQUESTS = 8
#: served tokens below which the comparison says too little to count
MIN_COMPARED = 64

for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import registry  # noqa: E402


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Programs compiled or loaded, persistent-cache hits, and the names
    of the programs that missed the persistent cache, through
    ``jax.monitoring`` and the compiler's log (process-wide listeners,
    registered once)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.misses: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        log = logging.getLogger("jax._src.compiler")
        log.addHandler(_MissHandler(self.misses))
        log.setLevel(logging.DEBUG)
        log.propagate = False

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def missed(self) -> str:
        top = sorted(self.misses.items(), key=lambda kv: -kv[1])[:8]
        return (f"{sum(self.misses.values())} "
                + json.dumps(dict(top), separators=(",", ":")))


class _MissHandler(logging.Handler):
    """Counts the compiler log's persistent-cache misses by program."""

    def __init__(self, misses: Dict[str, int]):
        super().__init__()
        self.misses = misses

    def emit(self, record) -> None:
        if str(record.msg).startswith("PERSISTENT COMPILATION CACHE MISS"):
            name = str(record.args[0])
            self.misses[name] = self.misses.get(name, 0) + 1


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def obs_record(events, requests: Dict[str, Dict]) -> Dict:
    """The request tracer's events the per-layer metrics read."""
    admit, prefill, decode = {}, [], []
    for ts, dur, ph, cat, name, scope, args in events:
        if name == "admit":
            admit.setdefault(scope, ts)
        elif name == "prefill" and ph == "X":
            r = requests.get(scope)
            cached = (r or {}).get("cached_len") or 0
            prefill.append({"t0": ts, "dur": dur,
                            "computed": args["prompt_len"] - cached})
        elif name == "decode_step" and ph == "X":
            decode.append({"t0": ts, "dur": dur, "batch": args["batch"]})
    return {"admit": admit, "prefill": prefill, "decode_step": decode}


def sample(outputs: Dict, seed: int) -> List:
    """The requests compared with the reference: the longest finished one
    and others drawn from the seed, up to ``SAMPLE_TOKENS`` served tokens;
    unfinished ones only where too few finished."""
    import numpy as np
    done = sorted((rid for rid, (_, s, d) in outputs.items()
                   if d and len(s) >= 2), key=str)
    live = sorted((rid for rid, (_, s, d) in outputs.items()
                   if not d and len(s) >= 2), key=str)
    g = np.random.default_rng([seed % (1 << 63), 99])
    picked, served = [], 0
    if done:
        longest = max(done, key=lambda r: (len(outputs[r][0])
                                           + len(outputs[r][1]), r))
        picked.append(longest)
        served += len(outputs[longest][1])
    for pool in (done, live):
        for i in g.permutation(len(pool)):
            rid = pool[i]
            if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_REQUESTS:
                break
            if rid not in picked:
                picked.append(rid)
                served += len(outputs[rid][1])
    return picked


def check(outputs, tracked, picked, arch, m: Dict, vocab: int, seed: int,
          control: bool, limit: float) -> Dict:
    """The comparison with the architecture's reference over the sample,
    each number beside its limit.  With ``control`` the float8 control
    stands in the program's place: the tokens judged are the ones it puts
    first."""
    wrong_len = sum(1 for rid, (_, s, d) in outputs.items()
                    if d and len(s) != tracked[rid].spec.max_new + 1)
    outside = sum(1 for _, s, _ in outputs.values()
                  for t in s if not 0 <= t < vocab)
    w = arch.weights(m, seed)
    gap, own, n = 0.0, 0.0, 0
    for rid in picked:
        prompt, served, _ = outputs[rid]
        g, c = arch.served_gaps(w, m, prompt, served, control=control)
        gap, own, n = max(gap, c if control else g), max(own, g), \
            n + len(served)
    del w
    if control:
        say(f"control in the program's place; the program's own "
            f"max_logit_gap on the same sample: {own}")
    return {
        "max_logit_gap": {"value": gap, "limit": limit},
        "tokens_compared": {"value": n, "limit": MIN_COMPARED},
        "finished_wrong_length": {"value": wrong_len, "limit": 0},
        "tokens_outside_vocab": {"value": outside, "limit": 0},
    }


#: checks whose limit is a least value; every other limit is a most
AT_LEAST = {"tokens_compared"}


def passed(checks: Dict) -> bool:
    """Every number within its limit (a NaN is within none)."""
    return all(c["value"] >= c["limit"] if k in AT_LEAST
               else c["value"] <= c["limit"] for k, c in checks.items())


def main(argv: Optional[List[str]] = None, *, require_tpu: bool = True,
         mesh=None, root: str = ROOT, bench_dir: str = HERE,
         persistent_cache: bool = True) -> int:
    """One run; the keyword arguments are for the benchmark's own tests,
    which skip the look for a chip and the persistent cache."""
    args = parse(sys.argv[1:] if argv is None else argv)
    cell = registry.load_cell(args.workload, root=root, bench_dir=bench_dir)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        say(f"needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); no result")
        return 2
    if require_tpu and len(devices) < cell.chips:
        say(f"{args.workload} needs {cell.chips} chips; JAX found "
            f"{len(devices)}; no result")
        return 2
    peaks_table = registry.load_json(os.path.join(HERE, "peaks.json"))
    peaks = peaks_table.get(dev.device_kind)
    if peaks is None and require_tpu:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       "peaks.json")
    if persistent_cache:
        from repro.core.compile_cache import configure_persistent_cache
        cache_dir = configure_persistent_cache()
        # every program, however quick to compile, and no cap on the
        # cache's size (a cap evicts one cell's programs for another's),
        # so that only a checkout's first run compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
        say(f"persistent compilation cache: {cache_dir}")
    counter = CompileCounter()

    import driver
    from repro.configs.base import ModelConfig
    from repro.obs import trace as obs_trace

    say(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    arch = registry.arch(cell.config, bench_dir=bench_dir)
    cfg = ModelConfig(**arch.model_config_kwargs(cell.config))
    m = arch.numbers(cfg)
    opts = cell.config["serve"]
    program_seed = args.seed % (1 << 31)
    if mesh is None:
        from repro.launch.mesh import attached_mesh_spec
        mesh = attached_mesh_spec()
    cluster, handle = driver.serve(cfg, opts, seed=program_seed, mesh=mesh,
                                   name=args.workload)
    t_built = time.perf_counter()
    gen = registry.generator(cell.traffic["kind"], bench_dir=bench_dir)
    source = gen.make(cell.traffic, seed=args.seed, vocab=cfg.vocab_size,
                      seconds=args.seconds, max_batch=int(opts["max_batch"]))
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    run = driver.Run(handle, source, trace_dir=trace_dir)
    lengths = source.prompt_lengths()
    served = run.warm(lengths, source.max_tokens(), cfg.vocab_size)
    t_warm = time.perf_counter()
    run.fill_prefixes(source.prefixes())
    tracer = obs_trace.enable(1 << 22) if args.trace else None
    run.start_setup_requests(source.setup_requests())
    jax.block_until_ready(run.runner.store.k_pages)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.3f} s: build {t_built - T_START:.3f}, warm-up "
        f"{t_warm - t_built:.3f} ({served} requests over {len(lengths)} "
        f"prompt lengths), prefix fill and starting requests "
        f"{time.perf_counter() - t_warm:.3f}; compiles "
        f"so far {counter.compiles} in {counter.compile_s:.3f} s, "
        f"persistent-cache hits {counter.cache_hits}, misses "
        f"{counter.missed()}")

    c0, cs0 = counter.compiles, counter.compile_s
    pre0 = run.engine.stats.preempted
    cpu0 = time.thread_time()
    run.window_loop(args.seconds, TRACE_SECONDS if args.trace else 0.0)
    cpu = time.thread_time() - cpu0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    in_window = counter.compiles - c0
    if tracer is not None:
        obs_trace.disable()
    lat = sorted(run.lateness)
    say(f"window {run.window[1] - run.window[0]:.3f} s: backend compiles "
        f"in the window {in_window} ({counter.compile_s - cs0:.3f} s); "
        f"generator lateness max "
        f"{(lat[-1] if lat else 0.0) * 1e3:.3f} ms over {len(lat)} "
        f"submissions; steps {len(run.steps)}; decode steps "
        f"{len(run.decodes)}; prefills {len(run.prefills)}; preemptions "
        f"{run.engine.stats.preempted - pre0}; {stalls(run)}; main "
        f"thread's CPU time {cpu:.3f} s")

    requests = {}
    for rid, tr in run.tracked.items():
        requests[rid] = {"id": rid, "due": tr.due, "stamps": tr.stamps,
                         "prompt_len": tr.prompt_len,
                         "cached_len": tr.admitted_cached,
                         "failed": tr.req.state == "rejected"}
    record = {"window": run.window, "nominal_end": run.nominal_end,
              "seconds": args.seconds, "setup_s": setup_s,
              "requests": list(requests.values()), "steps": run.steps,
              "memory_peak_bytes": peak, "model": m, "peaks": peaks,
              "obs": obs_record(tracer.snapshot(), requests)
              if tracer is not None else {}}
    if tracer is not None and tracer.dropped:
        say(f"request tracer dropped {tracer.dropped} events")
    if args.trace and run.trace_window is not None:
        record["trace"] = _reduce_trace(run, trace_dir)
        add_work(record["trace"], arch, m)
    outputs = run.outputs()
    tracked = run.tracked
    attempted = sum(1 for r in record["requests"]
                    if record["window"][0] <= r["due"] < run.nominal_end)
    failed = sum(1 for r in record["requests"] if r["failed"])
    picked = sample(outputs, args.seed)

    # free the program's state before the reference runs
    handle.release()
    del run, handle, cluster
    gc.collect()
    say(f"device bytes in use after release: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
    t_ref = time.perf_counter()
    checks = check(outputs, tracked, picked, arch, m, cfg.vocab_size,
                   program_seed, bool(args.control),
                   float(cell.config["correct"]["max_logit_gap"]))
    checks["requests_failed"] = {"value": failed, "limit": 0}
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    correct = passed(checks)
    say(f"reference over {len(picked)} requests in "
        f"{time.perf_counter() - t_ref:.3f} s")

    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for spec in wanted:
        v = registry.metric_reader(spec["name"], bench_dir=bench_dir)(record)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    tr = record.get("trace")
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["ops"],
                               "idle_gaps": tr["idle_gaps"]}
        say(f"trace: {json.dumps({k: v for k, v in tr.items() if k not in ('decode_lens', 'prefills', 'ops', 'idle_gaps')})}")
    result["checks"] = checks
    for k, v in checks.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


def stalls(run) -> str:
    """The window's longest step and longest time between two steps, each
    with its offset from the window's start (the host's stalls)."""
    t0 = run.window[0]
    steps = [s for s in run.steps if s[0] >= t0]
    if not steps:
        return "no step"
    a, b = max(((a, b) for a, b, *_ in steps), key=lambda s: s[1] - s[0])
    gap, at = max(((s[0] - p[1], p[1]) for p, s in zip(steps, steps[1:])),
                  default=(0.0, t0))
    return (f"longest step {(b - a) * 1e3:.3f} ms at {a - t0:.3f} s, "
            f"longest time between steps {gap * 1e3:.3f} ms at "
            f"{at - t0:.3f} s")


def _reduce_trace(run, trace_dir: str) -> Dict:
    """The traced window's device numbers, with the harness's record of
    the decode steps and prefills inside it."""
    import trace_reduce
    t0, t1 = run.trace_window
    path = trace_reduce.find_trace(trace_dir)
    tr = trace_reduce.reduce(path) if path else {
        "busy_s": 0.0, "ops": [], "kernels": {}, "kernel_s": 0.0,
        "kernel_calls": 0, "spans": {}, "idle_gaps": []}
    tr["window_s"] = t1 - t0
    tr["decode_lens"] = [lens for a, b, lens in run.decodes
                         if t0 <= a and b <= t1]
    tr["prefills"] = [(n, cached) for a, b, _, n, cached in run.prefills
                      if t0 <= a and b <= t1]
    spans = tr.get("spans", {})
    tr["decode_s"] = spans.get("chipbench.decode", [0.0, 0])[0]
    tr["prefill_s"] = spans.get("chipbench.prefill", [0.0, 0])[0]
    return tr


def add_work(tr: Dict, arch, m: Dict) -> None:
    """The work of the traced steps, counted by the cell's architecture
    module: ``decode_flops`` over the decode steps, ``prefill_flops``
    over the prefills, and ``kernel_work[name] = [flops, bytes]`` over
    the decode steps for each kernel the module names."""
    tr["decode_flops"] = sum(arch.decode_flops(m, lens)
                             for lens in tr["decode_lens"])
    tr["prefill_flops"] = sum(arch.prefill_flops(m, cached, n)
                              for n, cached in tr["prefills"])
    tr["kernel_work"] = {}
    for name, work in arch.KERNELS.items():
        flops = byts = 0
        for lens in tr["decode_lens"]:
            f, b = work(m, lens)
            flops += f
            byts += b
        tr["kernel_work"][name] = [flops, byts]


if __name__ == "__main__":
    sys.exit(main())
