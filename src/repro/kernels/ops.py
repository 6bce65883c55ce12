"""Public entry points for the Pallas kernels, and THE dispatch decision.

``use_compiled_kernels`` is the one place that chooses: on a TPU backend
every wrapper runs its compiled Pallas kernel (``interpret=False``);
elsewhere it runs the kernel's jnp oracle from ``ref.py`` (or
``paged_attention_ref``).  No wrapper runs a kernel in interpret mode --
the interpreter is a correctness tool that tests ask for explicitly by
calling the kernel modules with ``interpret=True``.  The decision is made
at trace time, so it follows the backend a program is compiled for.
"""

from __future__ import annotations

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _paged
from repro.kernels import ref
from repro.kernels import rmsnorm as _rms
from repro.kernels import rwkv6_scan as _rwkv
from repro.kernels import ssd_scan as _ssd


def use_compiled_kernels() -> bool:
    """True where the compiled Pallas kernels run: a TPU backend."""
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q: (B, H, S, D); k, v: (B, KVH, S, D) -> (B, H, S, D).  On TPU the
    kernel pair is differentiated through its custom VJP."""
    if use_compiled_kernels():
        return _fa.flash_attention(q, k, v, causal, window, block_q, block_k,
                                   False)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v, valid_len, *, block_s: int = 512):
    if use_compiled_kernels():
        return _dec.decode_attention(q, k, v, valid_len, block_s=block_s,
                                     interpret=False)
    return ref.decode_attention_ref(q, k, v, valid_len)


def rwkv6_wkv(r, k, v, logw, u, *, chunk: int = 128):
    if use_compiled_kernels():
        return _rwkv.rwkv6_wkv(r, k, v, logw, u, chunk=chunk,
                               interpret=False)
    return ref.rwkv6_wkv_ref(r, k, v, logw, u)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128):
    if use_compiled_kernels():
        return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=False)
    return ref.ssd_ref(x, dt, a, b, c)


def rmsnorm(x, gain, *, eps: float = 1e-6, block_rows: int = 128):
    if use_compiled_kernels():
        return _rms.rmsnorm(x, gain, eps=eps, block_rows=block_rows,
                            interpret=False)
    return ref.rmsnorm_ref(x, gain, eps=eps)


def paged_attention(q, k_pages, v_pages, page_table, valid_len, *,
                    window: int = 0, ring: bool = False):
    if use_compiled_kernels():
        return _paged.paged_attention(q, k_pages, v_pages, page_table,
                                      valid_len, window=window, ring=ring,
                                      interpret=False)
    return _paged.paged_attention_ref(q, k_pages, v_pages, page_table,
                                      valid_len, window=window, ring=ring)
