"""Production training driver, on the resource-centric runtime API.

On a real TPU pod:   python -m repro.launch.train --arch mistral-nemo-12b
On this CPU host:    add --reduced to run a smoke-scale config with the
                     SAME code path (sizing, placement, materialization,
                     checkpoints, watchdog).

The driver no longer hand-wires materialize -> CompileCache -> Checkpointer:
it describes the application and submits it; the Cluster sizes it from
history (§9.3), places it (two-level scheduler), materializes it (locality
ladder), and the JaxExecutor runs the compiled step loop with async
checkpoints and crash recovery."""

from __future__ import annotations

import argparse

from repro.core.compile_cache import configure_persistent_cache
from repro.core.history import HistoryStore
from repro.core.materializer import MESHES
from repro.runtime import Application, Cluster, JaxExecutor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single_pod")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/zenix_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU smoke scale (same code path)")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    configure_persistent_cache()

    history = HistoryStore("artifacts/history")
    app = Application.train(args.arch, shape=args.shape,
                            reduced=args.reduced, steps=args.steps)
    cluster = Cluster(pods=1, mesh=MESHES[args.mesh], history=history,
                      executor=JaxExecutor(ckpt_dir=args.ckpt_dir,
                                           ckpt_every=args.ckpt_every,
                                           resume=args.resume))
    handle = cluster.submit(app)
    print(f"[plan] {handle.plan.describe()}")
    print(f"[placed] pod={handle.pod} "
          f"demand={handle.job.demand_bytes / 2**30:.2f} GiB")
    if handle.cursor:
        print(f"[resume] from step {handle.cursor}")

    while handle.cursor < args.steps:
        m = handle.step()
        i = handle.cursor - 1
        if m["straggled"]:
            print(f"[watchdog] step {i} straggled: {m['wall_s']:.2f}s")
        if i % 10 == 0:
            print(f"step {i}: loss={m['loss']:.4f} ({m['wall_s']:.2f}s)")
    handle.checkpoint()
    handle.release()
    history.save()
    print("[done]")


if __name__ == "__main__":
    main()
