"""Paper Fig. 23 + cold/warm table: environment-startup hiding.

BulkX hides RDMA-QP setup behind code loading and caches compilations per
component layout.  TPU analog: XLA compilation is the startup cost; the
compile cache + background prewarm hide it.

Measured for a small (but real, jitted+sharded-shape) step:
  * cold        : full lower+compile on the critical path
  * warm_cache  : layout-keyed cache hit
  * prewarmed   : compile overlapped with "current component running"
                  (background thread), critical path = cache wait only

Derived: critical-path milliseconds (paper reports 773ms -> 284ms -> 10ms
warm; shape differs, the ORDERING is the reproduced claim)."""

import time

import jax
import jax.numpy as jnp

try:
    from benchmarks.common import row
except ImportError:  # run as a script: benchmarks/ is sys.path[0]
    from common import row
from repro.core.compile_cache import CompileCache, plan_layout_key
from repro.core.materializer import SINGLE_POD, Plan


def _build_fn(width):
    def build():
        def f(x, w):
            for _ in range(4):
                x = jnp.tanh(x @ w)
            return x.sum()
        return jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, width), jnp.float32),
            jax.ShapeDtypeStruct((width, width), jnp.float32)).compile()
    return build


def main() -> None:
    cc = CompileCache()
    plan = Plan("bench", "train", SINGLE_POD)

    # cold
    key1 = plan_layout_key("bench", "s", "m", plan) + "/w256"
    t0 = time.perf_counter()
    cc.get_or_compile(key1, _build_fn(256))
    cold_ms = (time.perf_counter() - t0) * 1e3

    # warm cache hit
    t0 = time.perf_counter()
    cc.get_or_compile(key1, _build_fn(256))
    warm_ms = (time.perf_counter() - t0) * 1e3

    # prewarmed: background compile overlaps 'current component running'
    key2 = key1 + "/next"
    fut = cc.prewarm(key2, _build_fn(384))
    time.sleep(0.9)        # current component executes meanwhile
    t0 = time.perf_counter()
    cc.get_or_compile(key2, _build_fn(384))
    pre_ms = (time.perf_counter() - t0) * 1e3
    fut.result(timeout=10)     # re-raises a failed background compile

    row("fig23_startup/cold", cold_ms * 1e3, f"critical_path={cold_ms:.1f}ms")
    row("fig23_startup/warm_cache", warm_ms * 1e3,
        f"critical_path={warm_ms:.2f}ms")
    row("fig23_startup/prewarmed", pre_ms * 1e3,
        f"critical_path={pre_ms:.2f}ms;hidden_behind_exec=True")
    assert warm_ms < cold_ms and pre_ms < cold_ms


if __name__ == "__main__":
    main()
