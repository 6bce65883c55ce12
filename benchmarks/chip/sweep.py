"""Find the knee of a cell's configuration under a traffic mix: the
highest offered rate without a growing backlog.  Run once, on the chip,
when a cell is defined; the cell then offers a fixed rate.

    python3 benchmarks/chip/sweep.py --config mistral-nemo-12b-pp4 \\
        --traffic chat-unshared --rates 0.4,0.6,0.8 --seconds 40 --seed 1

For each session rate it serves the mix for ``--seconds`` in one process
and prints one JSON line: requests offered and finished per second, the
backlog (requests due but not yet admitted) at the window's close, and
time-to-first-token percentiles.  A rate is sustained while the backlog
stays within a batch and the offered and finished rates agree.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import registry  # noqa: E402
import tails  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.core.compile_cache import configure_persistent_cache
    configure_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    import driver
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import attached_mesh_spec

    config = registry.load_json(os.path.join(HERE, "configs",
                                             args.config + ".json"))
    traffic = registry.load_json(os.path.join(HERE, "traffic",
                                              args.traffic + ".json"))
    cfg = ModelConfig(**registry.arch(config).model_config_kwargs(config))
    opts = config["serve"]
    mesh = attached_mesh_spec()
    gen = registry.generator(traffic["kind"])
    for rate in [float(r) for r in args.rates.split(",")]:
        params = dict(traffic, session_rate_per_s=rate)
        source = gen.make(params, seed=args.seed, vocab=cfg.vocab_size,
                          seconds=args.seconds,
                          max_batch=int(opts["max_batch"]))
        cluster, handle = driver.serve(cfg, opts, seed=args.seed, mesh=mesh,
                                       name=f"sweep-{rate}")
        run = driver.Run(handle, source)
        run.warm(source.prompt_lengths(), source.max_tokens(),
                 cfg.vocab_size)
        run.fill_prefixes(source.prefixes())
        run.window_loop(args.seconds)
        t0, t1 = run.window
        reqs = list(run.tracked.values())
        due = [r for r in reqs if t0 <= r.due < run.nominal_end]
        waiting = sum(1 for r in due if r.req.first_token_at is None)
        done = sum(1 for r in reqs if r.done)
        ttft = [r.stamps[0] - r.due for r in due if r.stamps]
        record = {"window": run.window, "nominal_end": run.nominal_end,
                  "requests": [{"stamps": r.stamps} for r in reqs]}
        print(json.dumps({
            "session_rate_per_s": rate,
            "offered_req_s": len(due) / (t1 - t0),
            "finished_req_s": done / (t1 - t0),
            "backlog_at_close": waiting,
            "ttft_p50_ms": 1e3 * (tails.median(ttft) or 0.0),
            "ttft_p90_ms": 1e3 * (tails.nearest_rank(ttft, 90) or 0.0),
            "itl_p95_ms": 1e3 * (tails.nearest_rank(
                tails.itl_samples(record), 95) or 0.0),
            "due": len(due)}), flush=True)
        handle.release()
        del run, handle, cluster
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
