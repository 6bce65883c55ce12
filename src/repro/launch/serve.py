"""Production serving driver, on the resource-centric runtime API.

Default mode sizes/places the serving application and drives the
continuous-batching engine through the NullExecutor (pure admission /
paging / sizing behaviour, no model).  ``--reduced`` binds the JaxExecutor
instead: a smoke-scale model runs real prefill + batched decode through
the IDENTICAL submission path."""

from __future__ import annotations

import argparse

import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core import profiles as prof
from repro.core.compile_cache import configure_persistent_cache
from repro.core.history import HistoryStore
from repro.core.materializer import MESHES
from repro.runtime import Application, Cluster, JaxExecutor, NullExecutor
from repro.runtime.options import ScalePolicy, ServeOptions
from repro.serving.kv_cache import Request, pool_pages_for_budget


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--mesh", default="single_pod")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--policy", default="history",
                    choices=["history", "fixed", "peak"])
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "paged"],
                    help="serving ModelRunner (paged = KV in pool pages, "
                         "decode via the paged-attention kernel)")
    ap.add_argument("--private-pool", action="store_true",
                    help="opt out of the pod-shared page pool")
    ap.add_argument("--no-swa-rings", action="store_true",
                    help="paged backend: charge sliding-window layers "
                         "growing page tables instead of bounded rings "
                         "(accounting baseline; tokens are identical)")
    ap.add_argument("--no-alias-kv", action="store_true",
                    help="paged backend: give this tenant its own "
                         "pool-sized device KV arrays instead of "
                         "aliasing the pod's shared same-shape array "
                         "set (benchmark baseline; tokens identical)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged backend: refcounted copy-on-write prefix "
                         "cache -- repeated prompt prefixes reuse cached "
                         "KV pages and prefill computes only the suffix "
                         "(rejected on dense: no shareable page identity)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the front-end request "
                         "router (replicas share the pod pool and, on "
                         "the paged backend, one KV array set + params)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="let the autoscale control plane move the "
                         "replica count up to this bound "
                         "(target-tracking on windowed queue depth)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="stream Prometheus metrics on this port for "
                         "the run's duration (0 = ephemeral; implies "
                         "metrics recording)")
    ap.add_argument("--reduced", action="store_true",
                    help="real smoke-scale model via the JaxExecutor")
    ap.add_argument("--autoscale", action="store_true",
                    help="drive the repro.autoscale control plane: two "
                         "bursts with an idle gap; the app is parked "
                         "between them and transparently unparked")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record the full request-lifecycle trace and "
                         "write it here: .jsonl -> one event per line, "
                         "anything else -> Chrome/Perfetto trace JSON "
                         "(summarize with `python -m repro.obs PATH`)")
    ap.add_argument("--metrics-dump", action="store_true",
                    help="record latency histograms and print the "
                         "Prometheus text exposition at the end")
    args = ap.parse_args()
    configure_persistent_cache()
    if args.backend != "dense" and not args.reduced:
        ap.error("--backend needs --reduced: the default arm serves through "
                 "the NullExecutor (no model, no kernel path)")
    if args.prefix_cache and args.backend != "paged":
        ap.error("--prefix-cache needs --backend paged: the dense cache "
                 "has no page identity to share across requests")

    tracer = obs.enable() if args.trace else None
    if args.metrics_dump or args.metrics_port is not None:
        obs.enable_metrics()
    metrics_srv = None
    if args.metrics_port is not None:
        metrics_srv = obs.serve_metrics(port=args.metrics_port)
        print(f"[metrics] http://127.0.0.1:{metrics_srv.port}/metrics")

    cfg = get_config(args.arch)
    mesh_spec = MESHES[args.mesh]
    history = HistoryStore("artifacts/history")

    scale = None
    if args.max_replicas is not None:
        scale = ScalePolicy(min_replicas=1, max_replicas=args.max_replicas)
    try:
        if args.reduced:
            executor = JaxExecutor()
            opts = ServeOptions(backend=args.backend,
                                max_batch=min(args.max_batch, 4),
                                pool_pages=128, policy=args.policy,
                                replicas=args.replicas,
                                swa_rings=not args.no_swa_rings,
                                alias_kv=not args.no_alias_kv,
                                prefix_cache=args.prefix_cache,
                                private_pool=args.private_pool,
                                scale=scale)
            app = Application.serve(args.arch, reduced=True, serve=opts)
            prompt_rng = (8, 64)
            max_new = 16
        else:
            # KV budget: HBM left after weights on the serving slice
            kv_budget = int(mesh_spec.hbm_per_device
                            * mesh_spec.num_devices * 0.6
                            - prof.param_bytes(cfg))
            pages = pool_pages_for_budget(max(kv_budget, 1 << 30),
                                          cfg.num_layers, cfg.kv_dim)
            executor = NullExecutor()
            opts = ServeOptions(max_batch=args.max_batch, pool_pages=pages,
                                policy=args.policy,
                                replicas=args.replicas,
                                private_pool=args.private_pool,
                                scale=scale)
            app = Application.serve(args.arch, shape="decode_32k",
                                    serve=opts)
            prompt_rng = (64, 4096)
            max_new = 256
    except ValueError as e:              # typed-options cross-field rules
        ap.error(str(e))

    cluster = Cluster(pods=1, mesh=mesh_spec, history=history,
                      executor=executor)
    handle = cluster.submit(app)
    print(f"[plan] kv_shard_heads={handle.plan.kv_shard_heads} "
          f"kv_shard_seq={handle.plan.kv_shard_seq} "
          f"batch_axes={handle.plan.batch_axes}")
    print(f"[placed] pod={handle.pod} "
          f"demand={handle.job.demand_bytes / 2**30:.2f} GiB")

    rng = np.random.default_rng(0)
    if args.autoscale:
        cluster.enable_autoscale(idle_park_s=3.0, confirm_ticks=1)
        half = max(args.requests // 2, 1)
        for i in range(half):
            handle.submit_request(Request(f"r{i}",
                                          int(rng.integers(*prompt_rng)),
                                          int(rng.integers(16, max_new + 1))))
        handle.run(max_steps=1_000_000)
        for t in range(6):              # idle ticks: the parker fires
            cluster.tick(now=float(t))
        parks = [a for a in cluster.autoscaler.log if a["action"] == "park"]
        if parks:
            print(f"[autoscale] parked after idle: "
                  f"freed_pages={parks[-1]['freed_pages']} "
                  f"freed_bytes={parks[-1]['freed_bytes']}")
        print(f"[autoscale] parked={handle.parked} "
              f"pod_free={cluster.capacity()[handle.pod]['free_bytes']}")
        for i in range(half, args.requests):   # burst 2: transparent unpark
            handle.submit_request(Request(f"r{i}",
                                          int(rng.integers(*prompt_rng)),
                                          int(rng.integers(16, max_new + 1))))
        print(f"[autoscale] unparked on submit: parked={handle.parked}")
        stats = handle.run(max_steps=1_000_000)
    else:
        for i in range(args.requests):
            handle.submit_request(Request(f"r{i}",
                                          int(rng.integers(*prompt_rng)),
                                          int(rng.integers(16, max_new + 1))))
        stats = handle.run(max_steps=1_000_000)
    pool = handle.engine.pool
    if args.replicas > 1 or args.max_replicas is not None:
        rstats = handle.serving_stats().get("router", {})
        print(f"[router] replicas={handle.num_replicas} "
              f"dispatched={rstats.get('dispatched', 0)} "
              f"added={rstats.get('replicas_added', 0)} "
              f"removed={rstats.get('replicas_removed', 0)}")
    print(f"[done] completed={stats['completed']} "
          f"tokens={stats['tokens_generated']} "
          f"decode_steps={stats['decode_steps']} "
          f"preempted={stats['preempted']} "
          f"mean_ttft={stats['mean_ttft_s'] * 1e3:.2f}ms "
          f"mean_decode_step={stats['mean_decode_step_s'] * 1e3:.2f}ms")
    print(f"[pool] pages={pool.num_pages} peak_util={pool.utilization:.2f} "
          f"scaleups={pool.stats['scaleups']} "
          f"denials={pool.stats['denials']}")
    sstats = handle.serving_stats()
    if "shared_pool" in sstats:
        sp = sstats["shared_pool"]
        print(f"[pod-pool] pages={sp['num_pages']} "
              f"util={sp['utilization']:.2f} "
              f"cross_app_preempt={sp['cross_app_preemptions']}")
    sz = pool.sizing()
    print(f"[sizing/{args.policy}] init={sz.init:.0f} step={sz.step:.0f}")
    if tracer is not None:
        meta = {"arch": args.arch, "backend": args.backend,
                "requests": args.requests}
        if args.trace.endswith(".jsonl"):
            n = obs.write_jsonl(tracer, args.trace)
        else:
            n = obs.write_chrome_trace(tracer, args.trace, extra_meta=meta)
        print(f"[trace] {n} events -> {args.trace} "
              f"(dropped={tracer.dropped}; summarize: "
              f"python -m repro.obs {args.trace})")
        obs.disable()
    if args.metrics_dump:
        print("[metrics]")
        print(obs.current_metrics().render(), end="")
    if metrics_srv is not None:
        metrics_srv.stop()
    if args.metrics_dump or args.metrics_port is not None:
        obs.disable_metrics()
    handle.release()
    history.save()


if __name__ == "__main__":
    main()
