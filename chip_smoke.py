"""Chip smoke: serve tinyllama-1.1b at full width on one TPU through the
normal serving path, and check what comes out.

    python chip_smoke.py          # from the repo root, on a TPU host

The path is the one users take: ``Cluster`` -> ``Application.serve`` ->
``JaxExecutor`` -> ``RequestRouter``/``ReplicaSet`` -> ``ServingEngine``
-> ``PagedRunner`` -> the compiled Pallas paged-attention kernel.  The
weights are random, made from a seed; the widths and depth are the
published ones.  One process drives the chip and starts no children.

* Phase A, plain paged serving: 8 requests of 32 new tokens, prompts of
  200 tokens (2 pages, native prefill) and 1,000 tokens (8 pages,
  chunked prefill).  All complete, exactly 256 tokens are generated, and
  every token id is inside the vocabulary.  On the live pool arrays of
  one decode step, ``ops.paged_attention`` (the compiled kernel) is
  compared with ``paged_attention_ref``, and the compiled decode program
  must contain the kernel.
* Phase B, the paper's mechanisms: prefix cache and two replicas, 8
  requests sharing a 1,024-token prefix; the app is parked mid-flight and
  4 more requests unpark it.  All complete, the prefix hit rate is
  positive and the park receipt frees pages.

Earlier lines are smoke output, not metrics.  The last line is one JSON
object naming the device; it is printed only when every check passed.
Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core.materializer import MeshSpec  # noqa: E402
from repro.runtime import Application, Cluster, JaxExecutor  # noqa: E402
from repro.runtime.options import ServeOptions  # noqa: E402
from repro.serving.kv_cache import PAGE_SIZE, Request  # noqa: E402

ARCH = "tinyllama-1.1b"
SEED = 0
NEW_TOKENS = 32
MAX_BATCH = 8
POOL_PAGES = 512
#: phase A prompt lengths: 2 pages (native prefill) and 8 pages (chunked)
SHORT_PROMPT, LONG_PROMPT = 200, 1000
#: phase B: shared prefix plus a private suffix per request
PREFIX_LEN, SUFFIX_LEN = 1024, 100
#: kernel vs oracle: both read bf16 K/V and accumulate in f32, but the
#: kernel's online softmax sums page by page while the oracle takes one
#: full softmax, and both round the output to bf16 (relative step 2**-8);
#: a few output ulps of O(1) values is ~1e-2
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _shape(name: str, max_len: int) -> ShapeConfig:
    """The invocation class of the smoke's own traffic: ``MAX_BATCH``
    sequences of at most ``max_len`` tokens, rounded up to whole pages, so
    admission is judged on what the smoke really holds."""
    pages = -(-max_len // PAGE_SIZE)
    return ShapeConfig(name, "decode", pages * PAGE_SIZE, MAX_BATCH)


def _serve(cfg: ModelConfig, mesh: MeshSpec, name: str, max_len: int,
           **opts):
    """Submit one paged serve app on a fresh one-pod cluster over
    ``mesh``; no history, so nothing outside the checkout feeds sizing."""
    cluster = Cluster(pods=1, mesh=mesh, history=None,
                      executor=JaxExecutor(seed=SEED))
    app = Application.serve(
        cfg, shape=_shape(f"{name}_decode", max_len), name=name,
        serve=ServeOptions(backend="paged", max_batch=MAX_BATCH,
                           pool_pages=POOL_PAGES, **opts))
    handle = cluster.submit(app)
    if handle.state != "running":
        raise RuntimeError(f"{name}: not admitted (state={handle.state}, "
                           f"demand={handle.job.demand_bytes} bytes, "
                           f"HBM/device={mesh.hbm_per_device})")
    return handle


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> tuple:
    return tuple(int(t) for t in rng.integers(0, vocab, n))


def _check_outputs(reqs, vocab: int) -> None:
    for r in reqs:
        out = r.output_tokens
        if out is None or len(out) != r.max_new_tokens + 1:
            raise AssertionError(f"{r.req_id}: output {out!r} is not "
                                 f"{r.max_new_tokens} decoded tokens "
                                 "after the prefill token")
        bad = [t for t in out if not 0 <= t < vocab]
        if bad:
            raise AssertionError(f"{r.req_id}: token ids {bad} outside "
                                 f"the vocabulary of {vocab}")


def hbm_in_use():
    """Device bytes in use now, where the backend reports it."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def check_kernel(runner, running) -> float:
    """Compare ``ops.paged_attention`` (the compiled kernel on TPU) with
    ``paged_attention_ref`` on the live pool arrays, through the page
    tables of the next decode step over ``running``.  Returns the
    largest absolute difference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention_ref

    args = runner.decode_inputs(running)
    table, vlen = args[5], args[7]
    cfg = runner.cfg
    q = jax.random.normal(jax.random.PRNGKey(SEED),
                          (runner.max_batch, cfg.num_heads, cfg.head_dim),
                          jnp.float32).astype(jnp.bfloat16)
    worst = 0.0
    for layer in sorted({0, runner.num_layers - 1}):
        kp, vp = runner.k_pages[layer], runner.v_pages[layer]
        got = np.asarray(jax.jit(ops.paged_attention)(q, kp, vp, table,
                                                      vlen), np.float32)
        want = np.asarray(jax.jit(paged_attention_ref)(q, kp, vp, table,
                                                       vlen), np.float32)
        if not np.isfinite(got).all():
            raise AssertionError(f"layer {layer}: kernel output not finite")
        np.testing.assert_allclose(got, want, **KERNEL_TOL,
                                   err_msg=f"paged attention, layer {layer}")
        worst = max(worst, float(np.abs(got - want).max()))
    return worst


def decode_program_text(runner, running) -> str:
    """Optimized HLO of the runner's compiled decode step for
    ``running`` (what the serving path executes)."""
    args = runner.decode_inputs(running)
    return runner._decode.lower(runner.params, *args, runner.k_pages,
                                runner.v_pages).compile().as_text()


def phase_a(cfg: ModelConfig, mesh: MeshSpec) -> dict:
    """Plain paged serving of 8 requests; kernel check on live tables."""
    from repro.kernels import ops

    t0 = time.perf_counter()
    handle = _serve(cfg, mesh, "smoke-a", LONG_PROMPT + NEW_TOKENS)
    t_setup = time.perf_counter() - t0
    hbm_setup = hbm_in_use()
    rng = np.random.default_rng(SEED)
    reqs = []
    for i in range(8):
        n = SHORT_PROMPT if i % 2 == 0 else LONG_PROMPT
        reqs.append(Request(f"a{i}", n, NEW_TOKENS,
                            prompt_tokens=_tokens(rng, n, cfg.vocab_size)))
    for r in reqs:
        handle.submit_request(r)
    runner, engine = handle.runner, handle.engine
    handle.step()               # admit + prefill all 8, one decode step
    if len(engine.running) != len(reqs):
        raise AssertionError(f"phase A: {len(engine.running)} of "
                             f"{len(reqs)} requests running after step 1")
    kernel_err = check_kernel(runner, engine.running)
    traces = runner.decode_traces
    if ops.use_compiled_kernels() and "tpu_custom_call" not in \
            decode_program_text(runner, engine.running):
        raise AssertionError("the compiled decode program has no "
                             "tpu_custom_call: the kernel is not on the "
                             "serving path")
    check_traces = runner.decode_traces - traces    # not serving compiles
    stats = handle.run()
    wall = time.perf_counter() - t0
    if stats["completed"] != len(reqs):
        raise AssertionError(f"phase A: {stats['completed']} of "
                             f"{len(reqs)} requests completed")
    if stats["tokens_generated"] != len(reqs) * NEW_TOKENS:
        raise AssertionError(f"phase A: {stats['tokens_generated']} tokens "
                             f"generated, expected {len(reqs) * NEW_TOKENS}")
    _check_outputs(reqs, cfg.vocab_size)
    out = {"wall_s": wall, "setup_s": t_setup, "hbm_after_setup": hbm_setup,
           "completed": stats["completed"],
           "tokens": stats["tokens_generated"],
           "decode_traces": runner.decode_traces - check_traces,
           "prefill_traces": runner.prefill_traces,
           "params_bytes": _tree_bytes(runner.params),
           "pool_bytes": runner.store.device_bytes(),
           "kernel_max_abs_err": kernel_err}
    handle.release()
    return out


def phase_b(cfg: ModelConfig, mesh: MeshSpec) -> dict:
    """Prefix cache + two replicas; park mid-flight, unpark on submit."""
    t0 = time.perf_counter()
    plen = PREFIX_LEN + SUFFIX_LEN
    handle = _serve(cfg, mesh, "smoke-b", plen + NEW_TOKENS,
                    prefix_cache=True, replicas=2)
    hbm_setup = hbm_in_use()
    rng = np.random.default_rng(SEED + 1)
    prefix = _tokens(rng, PREFIX_LEN, cfg.vocab_size)
    reqs = [Request(f"b{i}", plen, NEW_TOKENS,
                    prompt_tokens=prefix + _tokens(rng, SUFFIX_LEN,
                                                   cfg.vocab_size))
            for i in range(12)]
    # the first request prefills (and inserts the prefix) on its own, so
    # the next seven find it cached instead of racing it in one step
    handle.submit_request(reqs[0])
    handle.step()
    for r in reqs[1:8]:
        handle.submit_request(r)
    handle.step()
    handle.step()
    replicas = handle.num_replicas
    receipt = handle.park()
    hbm_parked = hbm_in_use()
    if not handle.parked:
        raise AssertionError("phase B: park left the app unparked")
    if receipt["freed_pages"] <= 0:
        raise AssertionError(f"phase B: park receipt freed no pages: "
                             f"{receipt}")
    for r in reqs[8:]:
        handle.submit_request(r)        # unparks transparently
    if handle.parked:
        raise AssertionError("phase B: submit did not unpark the app")
    stats = handle.run()
    wall = time.perf_counter() - t0
    if stats["completed"] != len(reqs):
        raise AssertionError(f"phase B: {stats['completed']} of "
                             f"{len(reqs)} requests completed")
    _check_outputs(reqs, cfg.vocab_size)
    view = handle.stats_view.cumulative()
    hit_rate = view.get("prefix_hit_rate", 0.0)
    if not hit_rate > 0:
        raise AssertionError(f"phase B: prefix hit rate {hit_rate}")
    out = {"wall_s": wall, "hbm_after_setup": hbm_setup,
           "hbm_parked": hbm_parked, "completed": stats["completed"],
           "tokens": stats["tokens_generated"], "replicas": replicas,
           "prefix_hit_rate": hit_rate,
           "park_freed_pages": receipt["freed_pages"],
           "park_freed_bytes": receipt["freed_bytes"],
           "park_drained": receipt["drained_requests"],
           "park_migrated": receipt.get("migrated_requests", 0),
           "decode_traces": handle.runner.decode_traces,
           "prefill_traces": handle.runner.prefill_traces}
    handle.release()
    return out


class CompileCounter:
    """Counts backend compiles and persistent-cache hits through
    ``jax.monitoring`` (process-wide listeners, registered once)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main() -> int:
    import jax

    from repro.core.compile_cache import configure_persistent_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    cache_dir = configure_persistent_cache()     # before the first compile
    counter = CompileCounter()
    from repro.launch.mesh import attached_mesh_spec
    mesh = attached_mesh_spec()
    say(f"smoke output, not metrics: device={dev.device_kind} "
        f"count={len(devices)} hbm/device={mesh.hbm_per_device} "
        f"compile cache={cache_dir}")
    cfg = get_config(ARCH)
    a = phase_a(cfg, mesh)
    say(f"phase A: {json.dumps(a)}")
    gc.collect()
    say(f"between phases: hbm_in_use={hbm_in_use()}")
    b = phase_b(cfg, mesh)
    say(f"phase B: {json.dumps(b)}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say(f"backend compiles={counter.compiles} "
        f"compile_s={counter.compile_s} "
        f"persistent cache hits={counter.cache_hits} "
        f"peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
