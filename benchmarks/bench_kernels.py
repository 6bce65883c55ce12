"""Kernel microbenchmarks: Pallas (interpret mode on CPU -- correctness
path; TPU timings require hardware) vs the jnp reference, small shapes.

Derived: max-abs error vs the oracle (the deployable signal from CPU)."""

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import block, row, timeit
except ImportError:  # run as a script: benchmarks/ is sys.path[0]
    from common import block, row, timeit
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import rmsnorm as _rms
from repro.kernels import rwkv6_scan as _rwkv
from repro.kernels import ssd_scan as _ssd

RNG = np.random.default_rng(0)


def ra(*s, scale=1.0):
    return jnp.asarray(RNG.standard_normal(s) * scale, jnp.float32)


def main() -> None:
    # flash attention
    q, k, v = ra(1, 4, 256, 64), ra(1, 2, 256, 64), ra(1, 2, 256, 64)
    f_kern = jax.jit(lambda q, k, v: _fa.flash_attention(q, k, v, True, 0,
                                                         128, 128, True))
    f_ref = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v,
                                                            causal=True))
    err = float(jnp.max(jnp.abs(f_kern(q, k, v) - f_ref(q, k, v))))
    us = timeit(lambda: block(f_ref(q, k, v)), iters=5)
    row("kernel/flash_attention_ref_b1h4s256", us, f"kernel_err={err:.2e}")

    # decode attention
    q1, k1, v1 = ra(4, 8, 64), ra(4, 2, 1024, 64), ra(4, 2, 1024, 64)
    vl = jnp.asarray(1024, jnp.int32)
    d_kern = jax.jit(lambda a, b, c: _dec.decode_attention(
        a, b, c, vl, interpret=True))
    d_ref = jax.jit(lambda a, b, c: ref.decode_attention_ref(a, b, c, vl))
    err = float(jnp.max(jnp.abs(d_kern(q1, k1, v1) - d_ref(q1, k1, v1))))
    us = timeit(lambda: block(d_ref(q1, k1, v1)), iters=10)
    row("kernel/decode_attention_ref_b4s1024", us, f"kernel_err={err:.2e}")

    # rwkv6
    r, k2, v2 = ra(1, 4, 256, 32, scale=.5), ra(1, 4, 256, 32, scale=.5), \
        ra(1, 4, 256, 32, scale=.5)
    lw = -jnp.exp(ra(1, 4, 256, 32, scale=.5) - 1)
    u = ra(4, 32, scale=.3)
    # chunk 32: beyond ~32 steps the pairwise-decay exponent range
    # exceeds fp32 headroom at this decay scale (documented saturation
    # limit, DESIGN.md §7) -- tests/test_kernels.py sweeps chunks 16-32
    kk = jax.jit(lambda *a: _rwkv.rwkv6_wkv(*a, chunk=32, interpret=True)[0])
    rr = jax.jit(lambda *a: ref.rwkv6_wkv_ref(*a)[0])
    err = float(jnp.max(jnp.abs(kk(r, k2, v2, lw, u) - rr(r, k2, v2, lw, u))))
    us = timeit(lambda: block(rr(r, k2, v2, lw, u)), iters=3)
    row("kernel/rwkv6_wkv_ref_s256", us, f"kernel_err={err:.2e}")

    # ssd
    x = ra(1, 4, 256, 16, scale=.5)
    dt = jnp.abs(ra(1, 4, 256, scale=.3)) + .1
    a = -jnp.abs(ra(1, 4, 256, scale=.3)) * dt
    b, c = ra(1, 256, 8, scale=.5), ra(1, 256, 8, scale=.5)
    sk = jax.jit(lambda *t: _ssd.ssd_scan(*t, chunk=64, interpret=True)[0])
    sr = jax.jit(lambda *t: ref.ssd_ref(*t)[0])
    err = float(jnp.max(jnp.abs(sk(x, dt, a, b, c) - sr(x, dt, a, b, c))))
    us = timeit(lambda: block(sr(x, dt, a, b, c)), iters=3)
    row("kernel/ssd_scan_ref_s256", us, f"kernel_err={err:.2e}")

    # rmsnorm
    xx, g = ra(512, 512), ra(512, scale=.1)
    nk = jax.jit(lambda a, b: _rms.rmsnorm(a, b, interpret=True))
    nr = jax.jit(lambda a, b: ref.rmsnorm_ref(a, b))
    err = float(jnp.max(jnp.abs(nk(xx, g) - nr(xx, g))))
    us = timeit(lambda: block(nr(xx, g)), iters=10)
    row("kernel/rmsnorm_ref_512x512", us, f"kernel_err={err:.2e}")


if __name__ == "__main__":
    main()
