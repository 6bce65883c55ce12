"""Paper Fig. 21: adaptive placement -- local / remote-scale / disagg.

The paper runs a fan-in ReduceBy with data components local, partially
remote, or fully disaggregated, showing I/O movement dominating as more
components go remote.  TPU analog on a decode cell's KV data component:

  * local        : KV heads co-located with their attention computes
                   (head-sharded; zero cross-chip KV traffic)
  * remote-scale : KV sequence-sharded; partial-softmax combines cross chips
  * disagg       : KV fully replicated-remote (batch-only sharding; every
                   access crosses the ICI)

Measured from fresh dry-run lowerings of whisper-base decode (small, fast
compile).  Derived: collective bytes/device + roofline collective term."""

import json
import os
import subprocess
import sys

try:
    from benchmarks.common import row
except ImportError:  # run as a script: benchmarks/ is sys.path[0]
    from common import row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def main() -> None:
    # run in a subprocess: needs the 512-device dry-run environment
    code = r"""
import json
from repro.configs.base import SHAPES
from repro.core.materializer import MESHES
from repro.launch.mesh import make_mesh_from_spec
from repro.launch.dryrun import lower_cell, collective_stats, memory_footprint
from repro.runtime import Application, Cluster, NullExecutor
import jax

shape = SHAPES["decode_32k"]
spec = MESHES["single_pod"]
mesh = make_mesh_from_spec(spec)
cluster = Cluster(pods=1, mesh=spec, executor=NullExecutor())
variants = {
  "local_headshard":  {"kv_shard_heads": True,  "kv_shard_seq": False},
  "remote_seqshard":  {"kv_shard_heads": False, "kv_shard_seq": True},
  "disagg_replicated":{"kv_shard_heads": False, "kv_shard_seq": False},
}
out = {}
for name, ov in variants.items():
    # each variant is one submitted invocation class; the handle carries
    # the materialized plan the dry-run lowers
    h = cluster.submit(Application.serve("whisper-base", shape=shape),
                       overrides=ov)
    l, _ = lower_cell(h.app.config, shape, h.plan, mesh)
    c = l.compile()
    cs = collective_stats(c.as_text())
    mem = memory_footprint(c)
    out[name] = {
        "coll_bytes": sum(d["bytes"] for d in cs.values()),
        "coll_counts": {k: d["count"] for k, d in cs.items() if d["count"]},
        "peak": mem["peak_tpu_adjusted"],
    }
    h.release()
    jax.clear_caches()
print("RESULT" + json.dumps(out))
"""
    # the child is a host-only dry run: pin it to the CPU, so it never
    # reaches for an accelerator this process (or another) may hold
    env = dict(os.environ, PYTHONPATH=SRC, TF_CPP_MIN_LOG_LEVEL="3",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    payload = None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            payload = json.loads(line[len("RESULT"):])
    if r.returncode != 0 or payload is None:
        raise RuntimeError(f"placement dry run failed (exit {r.returncode}):"
                           f"\n{r.stderr[-2000:]}")
    for name, d in payload.items():
        term = d["coll_bytes"] / 50e9
        row(f"fig21_placement/{name}", term * 1e6,
            f"coll_bytes={d['coll_bytes']};peak={d['peak']/2**30:.2f}GiB;"
            f"counts={d['coll_counts']}".replace(",", "|"))


if __name__ == "__main__":
    main()
