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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.models import ImplConfig, build_model
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
    kv = _struct(one_chip, (cfg.num_kv_heads, pool, PAGE_SIZE, cfg.head_dim),
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
    kv = _struct(one_chip, (cfg.num_kv_heads, pool, PAGE_SIZE, cfg.head_dim),
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


# -- the pool is never moved whole -----------------------------------------
#
# Serving widths and pool sizes of the two benchmark deployments, 2 layers.
# The runner is built on a 1-page pool with weight shapes only; the
# programs are compiled for the real pool, whose size they do not bake in.

#: Phi-3-mini's published widths (its family here is the llama-style stack)
PHI3_WIDTHS = dict(name="phi3-mini-widths", d_model=3072, num_heads=32,
                   num_kv_heads=32, head_dim=96, d_ff=8192, vocab_size=32064)


def _serving_runner(arch, batch, layers=2):
    if arch == "phi3-mini":
        cfg = get_config("tinyllama-1.1b").scaled(num_layers=layers,
                                                  **PHI3_WIDTHS)
    else:
        cfg = get_config(arch).scaled(num_layers=layers)
    model = build_model(cfg, ImplConfig(remat="none"))
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return PagedRunner(cfg, pool_pages=1, max_batch=batch, params=params)


def _pool_structs(runner, one_chip, pool_pages):
    cfg = runner.cfg
    shape = (cfg.num_kv_heads, pool_pages + 1, PAGE_SIZE, cfg.head_dim)
    params = jax.tree.map(lambda x: _struct(one_chip, x.shape, x.dtype),
                          runner.params)
    kv = [_struct(one_chip, shape, jnp.bfloat16)
          for _ in range(runner.num_layers)]
    return params, kv, shape


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]")


def _pool_moves(text, shape):
    """Names of the copies and transposes whose output holds a whole
    layer's pool, in any order of its dimensions."""
    want = sorted(shape)
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if (m and re.match(r"(copy|transpose)", m.group(1))
                and sorted(int(x) for x in m.group(2).split(",") if x)
                == want):
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("arch,pool_pages,batch,width,per_array", [
    ("mistral-nemo-12b", 1200, 32, 64, 0),
    # hd 96: the compact default layout of the pool puts the page dimension
    # minor, and each array is converted to row-major for the Pallas calls
    # and back once a step; a third pass would show up here
    ("phi3-mini", 144, 16, 16, 2),
])
def test_decode_step_pool_copies(one_chip, monkeypatch, arch, pool_pages,
                                 batch, width, per_array):
    """The decode step writes its tokens and attends over the pool where
    it lies: no copy of the whole pool (beyond phi-3's layout
    conversions), and no temporary the size of one."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    runner = _serving_runner(arch, batch)
    params, kv, shape = _pool_structs(runner, one_chip, pool_pages)
    b = batch
    i32 = functools.partial(_i32, one_chip)
    compiled = jax.jit(runner._decode_fn, donate_argnums=(9, 10)).lower(
        params, i32(b, 1), i32(b, 1), i32(b), i32(b), i32(b), i32(b, width),
        i32(b, 1), i32(b), kv, kv).compile()
    moves = _pool_moves(compiled.as_text(), shape)
    assert len(moves) <= per_array * 2 * runner.num_layers, moves
    if per_array == 0:
        pool_bytes = 2 * math.prod(shape)   # bf16
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < pool_bytes, f"{temp} bytes of temporaries"
    _assert_fits(compiled)


@pytest.mark.parametrize("arch,prog,n,ctx", [
    ("mistral-nemo-12b", "chunk", 4, 16),
    # one page over a one-page context: indexing the pool's page axis
    # directly here had the compiler move the whole pool page-major
    ("mistral-nemo-12b", "chunk", 1, 1),
    ("phi3-mini", "prefill", 4, 0),
])
def test_prompt_programs_pool_copies(one_chip, monkeypatch, arch, prog, n,
                                     ctx):
    """Prompt programs scatter and gather whole pages in place: nemo's
    chunks of ``n`` pages over ``ctx`` context pages, phi-3's 4-page
    native prefill."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    pool_pages = 1200 if arch == "mistral-nemo-12b" else 144
    runner = _serving_runner(arch, 1)
    compiled, shape = _compile_prompt(one_chip, runner, prog, n, ctx,
                                      pool_pages)
    assert _pool_moves(compiled.as_text(), shape) == []
    _assert_fits(compiled)


def _compile_prompt(one_chip, runner, prog, n, ctx, pool_pages):
    params, kv, shape = _pool_structs(runner, one_chip, pool_pages)
    i32 = functools.partial(_i32, one_chip)
    toks = i32(1, n * PAGE_SIZE)
    if prog == "chunk":
        lowered = jax.jit(runner._chunk_fn, donate_argnums=(8, 9)).lower(
            params, toks, i32(), i32(), i32(), i32(n), i32(), i32(ctx),
            kv, kv)
    else:
        lowered = jax.jit(runner._prefill_fn, donate_argnums=(6, 7)).lower(
            params, toks, i32(), i32(n), i32(0), i32(0), kv, kv)
    return lowered.compile(), shape


@pytest.mark.parametrize("prog,n,ctx", [("prefill", 4, 0), ("chunk", 4, 16)])
def test_prompt_program_code_does_not_grow_with_depth(one_chip, monkeypatch,
                                                      prog, n, ctx):
    """A prompt program holds one block's code however deep the stack:
    each prompt shape's program stays resident in HBM once it has run,
    and unrolled over phi-3's 32 layers a 4-page one held about 100 MB
    of code."""
    monkeypatch.setattr(ops, "use_compiled_kernels", lambda: True)
    code = {}
    for layers in (2, 16):
        runner = _serving_runner("phi3-mini", 1, layers)
        compiled, _ = _compile_prompt(one_chip, runner, prog, n, ctx, 144)
        code[layers] = compiled.memory_analysis().generated_code_size_in_bytes
    # unrolled, 16 layers held about 8 times the code of 2
    assert code[16] < 2 * code[2], code
