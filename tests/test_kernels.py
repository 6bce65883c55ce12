"""Per-kernel shape/dtype sweeps: pallas_call(interpret=True) vs ref.py
oracles (deliverable c: per-kernel allclose).  The kernels are called
through their modules with ``interpret=True`` stated: ``ops`` would run
the oracles themselves on this backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ops, ref
from repro.kernels import paged_attention as _paged
from repro.kernels import rmsnorm as _rms
from repro.kernels import rwkv6_scan as _rwkv
from repro.kernels import ssd_scan as _ssd

RNG = np.random.default_rng(7)


def ra(*shape, scale=1.0, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kvh,s,d", [
    (1, 2, 2, 128, 32),
    (2, 4, 2, 256, 64),
    (1, 8, 1, 256, 16),     # MQA
    (2, 2, 2, 192, 48),     # non-power-of-two s with block 64
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd(b, h, kvh, s, d, causal, window, dtype):
    q, k, v = ra(b, h, s, d, dtype=dtype), ra(b, kvh, s, d, dtype=dtype), \
        ra(b, kvh, s, d, dtype=dtype)
    o = _fa.flash_attention(q, k, v, causal, window, 64, 64, True)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_grads(causal, window):
    b, h, kvh, s, d = 2, 4, 2, 128, 32
    q, k, v = ra(b, h, s, d), ra(b, kvh, s, d), ra(b, kvh, s, d)

    def f(q, k, v):
        return (_fa.flash_attention(q, k, v, causal, window, 64, 64, True)
                ** 2).sum()

    def fr(q, k, v):
        o = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
        return (o.astype(jnp.float32) ** 2).sum()

    g = jax.grad(f, (0, 1, 2))(q, k, v)
    gr = jax.grad(fr, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_flash_matches_model_chunked_sdpa():
    """Kernel vs the model's chunked (flash-algorithm) jnp path."""
    from repro.models.attention import sdpa
    b, h, kvh, s, d = 1, 4, 2, 256, 32
    q, k, v = ra(b, s, h, d), ra(b, s, kvh, d), ra(b, s, kvh, d)
    o_model = sdpa(q, k, v, causal=True, impl="chunked", chunk=64)
    o_kernel = _fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), True, 0, 64, 64, True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o_model, np.float32),
                               np.asarray(o_kernel, np.float32),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kvh,s,d", [
    (2, 4, 2, 256, 32), (1, 8, 8, 512, 64), (3, 6, 2, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kvh, s, d, dtype):
    q = ra(b, h, d, dtype=dtype)
    k, v = ra(b, kvh, s, d, dtype=dtype), ra(b, kvh, s, d, dtype=dtype)
    vlen = jnp.asarray(RNG.integers(1, s, size=(b,)), jnp.int32)
    o = _dec.decode_attention(q, k, v, vlen, block_s=64, interpret=True)
    o_ref = ref.decode_attention_ref(q, k, v, vlen)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# rwkv6 / ssd scans vs exact per-step oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,hd,chunk", [
    (1, 2, 64, 8, 16), (2, 3, 128, 16, 32), (1, 1, 96, 32, 32)])
def test_rwkv6_wkv(b, h, s, hd, chunk):
    r, k, v = (ra(b, h, s, hd, scale=0.5) for _ in range(3))
    logw = -jnp.exp(ra(b, h, s, hd, scale=0.5) - 1.0)
    u = ra(h, hd, scale=0.3)
    o, st = _rwkv.rwkv6_wkv(r, k, v, logw, u, chunk=chunk, interpret=True)
    o_ref, st_ref = ref.rwkv6_wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_model_chunked_matches_oracle():
    """models/rwkv6.wkv_chunked (jnp) vs the per-step oracle."""
    from repro.models.rwkv6 import wkv_chunked
    b, h, s, hd = 2, 2, 64, 8
    r, k, v = (ra(b, s, h, hd, scale=0.5) for _ in range(3))
    logw = -jnp.exp(ra(b, s, h, hd, scale=0.5) - 1.0)
    u = ra(h, hd, scale=0.3)
    st0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    o, st = wkv_chunked(r, k, v, logw, u, st0, 16)
    tr = lambda t: t.transpose(0, 2, 1, 3)
    o_ref, st_ref = ref.rwkv6_wkv_ref(tr(r), tr(k), tr(v), tr(logw), u)
    np.testing.assert_allclose(np.asarray(tr(o)), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,h,s,p,n,chunk", [
    (1, 2, 64, 8, 4, 16), (2, 2, 128, 16, 8, 32)])
def test_ssd_scan(b, h, s, p, n, chunk):
    x = ra(b, h, s, p, scale=0.5)
    dt = jnp.abs(ra(b, h, s, scale=0.3)) + 0.1
    a = -jnp.abs(ra(b, h, s, scale=0.3)) * dt
    bmat, cmat = ra(b, s, n, scale=0.5), ra(b, s, n, scale=0.5)
    y, st = _ssd.ssd_scan(x, dt, a, bmat, cmat, chunk=chunk, interpret=True)
    y_ref, st_ref = ref.ssd_ref(x, dt, a, bmat, cmat)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_model_chunked_matches_oracle():
    from repro.models.mamba2 import ssd_chunked
    b, h, s, p, n = 1, 2, 64, 8, 4
    x = ra(b, s, h, p, scale=0.5)
    dt = jnp.abs(ra(b, s, h, scale=0.3)) + 0.1
    a_log = ra(h, scale=0.2)
    bmat, cmat = ra(b, s, n, scale=0.5), ra(b, s, n, scale=0.5)
    st0 = jnp.zeros((b, h, p, n), jnp.float32)
    y, st = ssd_chunked(x, dt, a_log, bmat, cmat, st0, 16)
    a = (-jnp.exp(a_log)[None, None] * dt)  # (b, s, h)
    tr3 = lambda t: t.transpose(0, 2, 1)
    tr4 = lambda t: t.transpose(0, 2, 1, 3)
    y_ref, st_ref = ref.ssd_ref(tr4(x), tr3(dt), tr3(a), bmat, cmat)
    np.testing.assert_allclose(np.asarray(tr4(y)), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(8, 64), (33, 128), (256, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rows, d, dtype):
    x = ra(rows, d, dtype=dtype)
    g = ra(d, scale=0.1)
    o = _rms.rmsnorm(x, g, interpret=True)
    o_ref = ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# paged attention (scalar-prefetch page tables); pools are head-major
# (KV, P, page, d), as the serving runner stores them
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kvh,d,pool,page,maxp", [
    (2, 4, 2, 32, 8, 64, 3), (1, 8, 8, 16, 12, 32, 5), (3, 6, 2, 64, 16, 64, 4)])
def test_paged_attention(b, h, kvh, d, pool, page, maxp):
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_ref)
    q = ra(b, h, d)
    kp, vp = ra(kvh, pool, page, d), ra(kvh, pool, page, d)
    tables = []
    for i in range(b):
        n = int(RNG.integers(1, maxp + 1))
        pages = RNG.choice(pool, size=n, replace=False)
        tables.append(list(pages) + [-1] * (maxp - n))
    table = jnp.asarray(tables, jnp.int32)
    vlen = jnp.asarray([(int((table[i] >= 0).sum())) * page
                        - int(RNG.integers(0, page)) for i in range(b)],
                       jnp.int32)
    o = paged_attention(q, kp, vp, table, vlen, interpret=True)
    o_ref = paged_attention_ref(q, kp, vp, table, vlen)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,kvh,d,pool,page,maxp", [
    (3, 4, 2, 32, 12, 64, 4), (2, 8, 4, 16, 10, 32, 3)])
def test_paged_ref_matches_dense_oracle(b, h, kvh, d, pool, page, maxp):
    """paged_attention_ref vs the model-layer dense decode attention
    (gqa_decode_sdpa) on random page tables and ragged valid_len: gather
    each request's pages into a contiguous cache and the two must agree."""
    from repro.kernels.paged_attention import paged_attention_ref
    from repro.models.attention import gqa_decode_sdpa

    q = ra(b, h, d)
    kp, vp = ra(kvh, pool, page, d), ra(kvh, pool, page, d)
    tables, vlens = [], []
    for _ in range(b):
        n = int(RNG.integers(1, maxp + 1))
        pages = RNG.choice(pool, size=n, replace=False)
        tables.append(list(pages) + [-1] * (maxp - n))
        vlens.append(n * page - int(RNG.integers(0, page)))  # ragged
    table = jnp.asarray(tables, jnp.int32)
    vlen = jnp.asarray(vlens, jnp.int32)
    o = paged_attention_ref(q, kp, vp, table, vlen)

    for i in range(b):
        own = [p for p in tables[i] if p >= 0]
        # gather this request's pages contiguously: (1, KV, S, d)
        k = kp[:, jnp.asarray(own)].reshape(1, kvh, len(own) * page, d)
        v = vp[:, jnp.asarray(own)].reshape(1, kvh, len(own) * page, d)
        k_valid = jnp.arange(len(own) * page) < vlens[i]
        o_dense = gqa_decode_sdpa(q[i:i + 1, None], k, v, k_valid)
        np.testing.assert_allclose(np.asarray(o[i]),
                                   np.asarray(o_dense[0, 0]),
                                   rtol=2e-5, atol=2e-5)


def test_paged_matches_contiguous_decode():
    """Paged kernel == dense decode kernel when pages are contiguous."""
    b, h, kvh, d, page, npg = 2, 4, 2, 32, 64, 4
    s = page * npg
    q = ra(b, h, d)
    k, v = ra(b, kvh, s, d), ra(b, kvh, s, d)
    vlen = jnp.asarray([s - 7, s // 2], jnp.int32)
    dense = _dec.decode_attention(q, k, v, vlen, block_s=page, interpret=True)
    # build a per-request page pool from the contiguous cache
    kp = k.reshape(b, kvh, npg, page, d).transpose(1, 0, 2, 3, 4).reshape(
        kvh, b * npg, page, d)
    vp = v.reshape(b, kvh, npg, page, d).transpose(1, 0, 2, 3, 4).reshape(
        kvh, b * npg, page, d)
    table = jnp.arange(b * npg, dtype=jnp.int32).reshape(b, npg)
    paged = _paged.paged_attention(q, kp, vp, table, vlen, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pos_last", [0, 5, 23, 24, 37, 100])
def test_paged_attention_ring_window(pos_last):
    """Ring-table sliding-window path (ATTN_LOCAL layers): kernel and
    jnp oracle must both match a dense windowed-attention reference when
    the ring contents are built by last-write-wins over the token
    history (exactly what decode does)."""
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_ref)
    page, ring_pages, kvh, h, d, window, pool = 8, 3, 2, 4, 16, 20, 10
    ring_tokens = ring_pages * page
    vlen = pos_last + 1
    keys = np.asarray(ra(vlen, kvh, d), np.float32)
    vals = np.asarray(ra(vlen, kvh, d), np.float32)
    kp = np.zeros((kvh, pool, page, d), np.float32)
    vp = np.zeros((kvh, pool, page, d), np.float32)
    ring_ids = [7, 2, 5][:min(ring_pages, -(-vlen // page))]
    table = np.full((1, ring_pages), -1, np.int32)
    table[0, :len(ring_ids)] = ring_ids
    for p in range(vlen):          # write each token at its ring slot
        pg, off = divmod(p % ring_tokens, page)
        if pg < len(ring_ids):
            kp[:, ring_ids[pg], off] = keys[p]
            vp[:, ring_ids[pg], off] = vals[p]
    q = np.asarray(ra(1, h, d), np.float32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray([vlen]))
    o_ref = paged_attention_ref(*args, window=window, ring=True)
    o_krn = paged_attention(*args, window=window, ring=True, interpret=True)
    # dense reference over the last `window` tokens
    lo = max(0, vlen - window)
    k = np.repeat(keys[lo:vlen], h // kvh, axis=1)
    v = np.repeat(vals[lo:vlen], h // kvh, axis=1)
    s = np.einsum("hd,shd->hs", q[0], k) * d ** -0.5
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o_dense = np.einsum("hs,shd->hd", pr, v)
    np.testing.assert_allclose(np.asarray(o_ref)[0], o_dense,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_krn)[0], o_dense,
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# kv_write: decode's one-token write into the head-major pool
# ---------------------------------------------------------------------------

_KVW_PAGE, _KVW_POOL = 128, 10         # page 9 is the trash page


def _ring_lanes():
    """Write slots of three requests decoding through 3-page rings of
    32-token pages: token ``p`` goes to ring page ``(p // 32) % 3`` at
    row ``p % 32``."""
    rings, pos, page = [[5, 0, 3], [1, 4, 2], [6, 8, 7]], [95, 130, 47], 32
    return ([r[(p // page) % 3] for r, p in zip(rings, pos)],
            [p % page for p in pos], page)


@pytest.mark.parametrize("case", ["offsets", "ring", "padded"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_write(case, dtype):
    """Each lane's row lands at ``[:, phys, off]``; the other rows of its
    16-row block and every other page are unchanged (padded lanes all
    write the trash page, whose content is never read)."""
    page, trash = _KVW_PAGE, _KVW_POOL - 1
    if case == "offsets":                 # block edges and the last row
        phys, off = [2, 0, 4, 1], [0, 15, 16, 127]
    elif case == "ring":
        phys, off, page = _ring_lanes()
    else:                                 # 2 real lanes, 3 padded
        phys, off = [3, 1, trash, trash, trash], [40, 9, 0, 0, 0]
    kvh, d, b = 2, 128, len(phys)
    kp, vp = (ra(kvh, _KVW_POOL, page, d, dtype=dtype) for _ in range(2))
    kn, vn = ra(b, kvh, d, dtype=dtype), ra(b, kvh, d, dtype=dtype)
    args = (kn, vn, jnp.asarray(phys, jnp.int32),
            jnp.asarray(off, jnp.int32))
    got = _paged.kv_write(kp, vp, *args, interpret=True)
    want = _paged.kv_write_ref(kp, vp, *args)
    for pool, new, g_, w_ in zip((kp, vp), (kn, vn), got, want):
        g_, w_ = np.asarray(g_, np.float32), np.asarray(w_, np.float32)
        expect = np.asarray(pool, np.float32).copy()
        for i, (p, o) in enumerate(zip(phys, off)):
            if p != trash:
                expect[:, p, o] = np.asarray(new[i], np.float32)
        np.testing.assert_array_equal(g_[:, :trash], expect[:, :trash])
        np.testing.assert_array_equal(w_[:, :trash], expect[:, :trash])


def test_ops_runs_the_oracles_off_tpu():
    """Off TPU every ``ops`` wrapper is its jnp oracle (exactly), so no
    serving path reaches the interpreter; on TPU ``use_compiled_kernels``
    sends them to the compiled kernels (tests/test_tpu_compile.py)."""
    assert not ops.use_compiled_kernels()
    b, h, kvh, d, pool, page = 2, 4, 2, 32, 6, 16
    q = ra(b, h, d)
    kp, vp = ra(kvh, pool, page, d), ra(kvh, pool, page, d)
    table = jnp.asarray([[3, 1, -1], [0, 5, 2]], jnp.int32)
    vlen = jnp.asarray([20, 40], jnp.int32)
    got = ops.paged_attention(q, kp, vp, table, vlen)
    want = _paged.paged_attention_ref(q, kp, vp, table, vlen)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    text = jax.jit(lambda *a: ops.paged_attention(*a)).lower(
        q, kp, vp, table, vlen).as_text()
    assert "pallas" not in text and "tpu_custom_call" not in text
    kn, vn = ra(b, kvh, d), ra(b, kvh, d)
    phys, off = jnp.asarray([4, 1]), jnp.asarray([3, 15])
    got = ops.kv_write(kp, vp, kn, vn, phys, off)
    want = _paged.kv_write_ref(kp, vp, kn, vn, phys, off)
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    text = jax.jit(lambda *a: ops.kv_write(*a)).lower(
        kp, vp, kn, vn, phys, off).as_text()
    assert "pallas" not in text and "tpu_custom_call" not in text
    x, g = ra(8, 64), ra(64, scale=0.1)
    np.testing.assert_array_equal(np.asarray(ops.rmsnorm(x, g)),
                                  np.asarray(ref.rmsnorm_ref(x, g)))
