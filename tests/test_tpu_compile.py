"""Compile the serving path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned tiles, too much fast memory, a program that does not fit).
The widths are the published ones; only depth is cut, to 2 layers.

The topology is described inside a module fixture, never at import, and
every test of this kind stays in this one file: only one process may
load the TPU library, and the worker given this file keeps it.  The
persistent compilation cache is off around these compiles, since an
entry written for a described chip cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.serving.kv_cache import PAGE_SIZE, PageGroups
from repro.serving.model_runner import PagedRunner

V5E_HBM = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _i32(one_chip, *shape):
    return _struct(one_chip, shape, jnp.int32)


def _assert_fits(compiled):
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert live < V5E_HBM, f"{live} bytes do not fit one v5e"


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mistral-nemo-12b"])
def test_paged_kernel_linear_table(one_chip, arch):
    cfg = get_config(arch)
    b, pool, width = 8, 257, 16
    kv = _struct(one_chip, (pool, PAGE_SIZE, cfg.num_kv_heads, cfg.head_dim),
                 jnp.bfloat16)
    compiled = jax.jit(functools.partial(paged_attention,
                                         interpret=False)).lower(
        _struct(one_chip, (b, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        kv, kv, _struct(one_chip, (b, width), jnp.int32),
        _struct(one_chip, (b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_kernel_ring_table(one_chip):
    cfg = get_config("gemma3-12b")
    ring = PageGroups.from_config(cfg).ring_pages
    b, pool = 8, 257
    kv = _struct(one_chip, (pool, PAGE_SIZE, cfg.num_kv_heads, cfg.head_dim),
                 jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        paged_attention, window=cfg.sliding_window, ring=True,
        interpret=False)).lower(
        _struct(one_chip, (b, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        kv, kv, _struct(one_chip, (b, ring), jnp.int32),
        _struct(one_chip, (b,), jnp.int32)).compile()
    assert ring == 9
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def runner():
    """Full tinyllama width, depth cut to 2 layers, the serving batch."""
    cfg = get_config("tinyllama-1.1b").scaled(num_layers=2)
    return PagedRunner(cfg, pool_pages=256, max_batch=8)


def _state_structs(runner, one_chip):
    def st(x):
        return _struct(one_chip, x.shape, x.dtype)
    return (jax.tree.map(st, runner.params),
            [st(a) for a in runner.k_pages], [st(a) for a in runner.v_pages])


def test_paged_decode_step(one_chip, runner, monkeypatch):
    """The runner's decode step, steered to the compiled kernel the way
    ``ops`` steers it on a TPU backend."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    params, kp, vp = _state_structs(runner, one_chip)
    b, width = runner.max_batch, 16
    i32 = functools.partial(_i32, one_chip)
    compiled = jax.jit(runner._decode_fn, donate_argnums=(9, 10)).lower(
        params, i32(b, 1), i32(b, 1), i32(b), i32(b), i32(b), i32(b, width),
        i32(b, 1), i32(b), kp, vp).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_paged_prefill_16_pages(one_chip, runner, monkeypatch):
    """A 16-page (2,048-token) native prefill.  Its attention is the plain
    jnp path (``attn.sdpa``), not a kernel: the test pins that it
    compiles and fits one chip."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    params, kp, vp = _state_structs(runner, one_chip)
    n = 16
    i32 = functools.partial(_i32, one_chip)
    compiled = jax.jit(runner._prefill_fn, donate_argnums=(6, 7)).lower(
        params, i32(1, n * PAGE_SIZE), i32(), i32(n), i32(0), i32(0),
        kp, vp).compile()
    _assert_fits(compiled)


def test_paged_chunk_step(one_chip, runner, monkeypatch):
    """One chunk of chunked prefill (4 pages over a 4-page context), the
    path long prompts and prefix-cache hits take."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    params, kp, vp = _state_structs(runner, one_chip)
    n, ctx = runner.chunk_pages, 4
    i32 = functools.partial(_i32, one_chip)
    compiled = jax.jit(runner._chunk_fn, donate_argnums=(8, 9)).lower(
        params, i32(1, n * PAGE_SIZE), i32(), i32(), i32(), i32(n), i32(),
        i32(ctx), kp, vp).compile()
    _assert_fits(compiled)
