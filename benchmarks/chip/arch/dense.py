"""The dense decoder: RMS norm, rotary position embedding (halves
rotated), grouped-query causal attention, RMS norm, SiLU-gated MLP,
residuals; then a final RMS norm and an untied head.  It is the
published Mistral / Phi-3 decoder layer; ``arch/mistral.py`` and
``arch/phi3.py`` take everything from here.

The program's configuration: only decoder-only, RoPE, SiLU-gated,
RMS-normed stacks of full attention are expressible; anything else is
refused rather than run as something it is not.

The plain reference imports nothing of the program.  It draws its own
weights from the seed, by the scheme the served model's weights are
documented to follow (each leaf of the parameter tree, in the tree's
sorted key order, from its own split of ``PRNGKey(seed)``: a float32
standard normal times 0.02, rounded to bfloat16; RMS-norm gains are
stored as an offset from 1 and start at 0), and computes in float32 at
``highest`` matmul precision from those bfloat16 values, layer by layer
and in blocks of query rows, so that it fits beside nothing else on the
chip.

The comparison: run the reference once over each sampled prompt with its
served tokens, and take the widest gap by which a served token's logit
lies below the reference's best at that position.  The control (never
run by the benchmark's own runs) is the reference put in the program's
place at the precision below the served one: every matmul's operands
rounded to float8 (e4m3, per-tensor scale for weights, per-row for
activations); its reading is the gap, under the float32 reference, of
the token it puts first.

The work a call needs, from its shapes: what a kernel or a step must
compute and move whatever implements it.  Counts are of the algorithm,
not of an implementation: padded batch lanes, masked page-table entries,
the pool's relayout and recomputed values are not work.  A multiply-add
is two operations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
#: query rows per attention block
Q_BLOCK = 512
F8_MAX = 448.0
#: bytes of one element as served (bfloat16 weights, KV and queries)
BF16 = 2


def model_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """``repro.configs.base.ModelConfig`` keyword arguments for a
    configuration file written in Hugging Face ``config.json`` keys."""
    act = config.get("hidden_act", "silu")
    if act != "silu":
        raise ValueError(f"{config['name']}: hidden_act {act!r} is not the "
                         "program's gated SiLU MLP")
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return dict(
        name=config["name"],
        family="dense",
        num_layers=int(config["num_hidden_layers"]),
        d_model=d,
        num_heads=heads,
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // heads),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        max_context=int(config["max_position_embeddings"]),
    )


def numbers(cfg) -> Dict:
    """The sizes a run records (``record["model"]``), from the program's
    ``ModelConfig``: what the reference and the work counts read."""
    return {"layers": cfg.num_layers, "d_model": cfg.d_model,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}


# -- the reference -----------------------------------------------------


def leaf_shapes(m: Dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, shape, std) of every weight, in sorted key order; layers
    are stacked on the leading axis."""
    L, d, h, kv, hd, ff, v = (m["layers"], m["d_model"], m["heads"],
                              m["kv_heads"], m["head_dim"], m["d_ff"],
                              m["vocab"])
    blk = "blocks/p0_attn_global/"
    return [
        (blk + "attn/wk", (L, d, kv, hd), STD),
        (blk + "attn/wo", (L, h, hd, d), STD),
        (blk + "attn/wq", (L, d, h, hd), STD),
        (blk + "attn/wv", (L, d, kv, hd), STD),
        (blk + "ln1/g", (L, d), 0.0),
        (blk + "ln2/g", (L, d), 0.0),
        (blk + "mlp/wi_gate", (L, d, ff), STD),
        (blk + "mlp/wi_up", (L, d, ff), STD),
        (blk + "mlp/wo", (L, ff, d), STD),
        ("embed/head", (d, v), STD),
        ("embed/tok", (v, d), STD),
        ("ln_f/g", (d,), 0.0),
    ]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def weights(m: Dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights, in bfloat16, keyed by path."""
    leaves = leaf_shapes(m)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = {}
    for key, (path, shape, std) in zip(keys, leaves):
        out[path] = (jnp.zeros(shape, jnp.bfloat16) if std == 0.0
                     else _draw(key, shape, std))
    return out


def _f8(x, axis):
    """Round to float8 e4m3 (3 mantissa bits, normal exponents from -6,
    largest value 448) with a scale over ``axis`` (None: the whole
    tensor), back in float32.  Rounded in float32 arithmetic, so it means
    the same on every backend."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    y = x / scale
    a = jnp.abs(y)
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9))), -6.0)
    step = jnp.exp2(e - 3.0)
    return jnp.sign(y) * jnp.minimum(jnp.round(a / step) * step,
                                     F8_MAX) * scale


def _mm(eq, x, w, f8: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if f8:
        x, w = _f8(x, -1), _f8(w, None)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "f8"))
def _layer(x, w, *, eps, theta, f8):
    """One decoder layer over a (S, d) sequence; ``w`` holds this layer's
    weights."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm("sd,dnh->snh", h, w["wq"], f8), pos, theta)
    k = _rope(_mm("sd,dnh->snh", h, w["wk"], f8), pos, theta)
    v = _mm("sd,dnh->snh", h, w["wv"], f8)
    heads, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    nb = s // Q_BLOCK if s % Q_BLOCK == 0 and s > Q_BLOCK else 1
    qb = q.reshape(nb, s // nb, heads, hd)

    def block(args):
        i, qi = args
        rows = i * (s // nb) + jnp.arange(s // nb)
        sc = jnp.einsum("qhd,khd->hqk", qi, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        sc = jnp.where(pos[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(s, heads, hd)
    x = x + _mm("snh,nhd->sd", o, w["wo"], f8)
    h = _rms(x, w["ln2"], eps)
    g = _mm("sd,df->sf", h, w["wi_gate"], f8)
    u = _mm("sd,df->sf", h, w["wi_up"], f8)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["wo2"], f8)


@functools.partial(jax.jit, static_argnames=("eps", "f8"))
def _logits(x, rows, g, head, *, eps, f8):
    return _mm("sd,dv->sv", _rms(x[rows], g, eps), head, f8)


def _bucket(n: int) -> int:
    return max(128, 1 << (n - 1).bit_length())


def logits(w: Dict, m: Dict, tokens: Sequence[int], rows: Sequence[int],
           *, f8: bool = False) -> np.ndarray:
    """Float32 logits at positions ``rows`` of the sequence ``tokens``
    (padded at the end to a power of two: causal attention keeps the
    padding out of every real position)."""
    n = len(tokens)
    s = _bucket(n)
    toks = np.zeros(s, np.int32)
    toks[:n] = tokens
    x = w["embed/tok"][jnp.asarray(toks)].astype(jnp.float32)
    blk = "blocks/p0_attn_global/"
    for i in range(m["layers"]):
        lw = {"ln1": w[blk + "ln1/g"][i], "ln2": w[blk + "ln2/g"][i],
              "wq": w[blk + "attn/wq"][i], "wk": w[blk + "attn/wk"][i],
              "wv": w[blk + "attn/wv"][i], "wo": w[blk + "attn/wo"][i],
              "wi_gate": w[blk + "mlp/wi_gate"][i],
              "wi_up": w[blk + "mlp/wi_up"][i],
              "wo2": w[blk + "mlp/wo"][i]}
        x = _layer(x, lw, eps=m["norm_eps"], theta=m["rope_theta"], f8=f8)
    r = np.zeros(_bucket(len(rows)), np.int32)
    r[:len(rows)] = rows
    out = _logits(x, jnp.asarray(r), w["ln_f/g"], w["embed/head"],
                  eps=m["norm_eps"], f8=f8)
    return np.asarray(out)[:len(rows)]


def served_gaps(w: Dict, m: Dict, prompt: Sequence[int],
                served: Sequence[int], *, control: bool = False
                ) -> Tuple[float, float]:
    """(widest gap of the served tokens, widest gap of the control's
    tokens) over one request: the reference over prompt + served tokens,
    read at each position that produced a served token.  The control's
    gap is computed only when ``control``; otherwise it is NaN."""
    seq = list(prompt) + list(served)
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    with jax.default_matmul_precision("highest"):
        ref = logits(w, m, seq[:-1], rows)
        best = ref.max(axis=1)
        got = ref[np.arange(len(rows)), np.asarray(served)]
        gap = float(np.max(best - got))
        cgap = float("nan")
        if control:
            low = logits(w, m, seq[:-1], rows, f8=True)
            pick = low.argmax(axis=1)
            cgap = float(np.max(best - ref[np.arange(len(rows)), pick]))
    return gap, cgap


# -- the work ----------------------------------------------------------


def paged_attention(m: Dict, lens: Sequence[int]) -> Tuple[int, int]:
    """(operations, bytes) of one decode step's paged-attention calls,
    one a layer, over the real lanes whose valid lengths are ``lens``:
    each lane's query against its live keys and values (scores and the
    weighted sum, ``4 * len * heads * head_dim``), reading those keys and
    values once and the query and output once."""
    live = sum(int(n) for n in lens)
    flops = 4 * live * m["heads"] * m["head_dim"]
    byts = (live * m["kv_heads"] * m["head_dim"] * 2 * BF16
            + len(lens) * m["heads"] * m["head_dim"] * 2 * BF16)
    return flops * m["layers"], byts * m["layers"]


#: each kernel's work in one decode step, by the kernel's name in the
#: trace (``trace_reduce``: the function that makes its ``pallas_call``)
KERNELS = {"paged_attention": paged_attention}


def layer_matmul_flops(m: Dict) -> int:
    """Operations of one token through one layer's weights: q, k, v and
    output projections and the gated MLP's three matrices."""
    d, h, kv, hd, ff = (m["d_model"], m["heads"], m["kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * d * (2 * h * hd + 2 * kv * hd) + 2 * 3 * d * ff


def head_flops(m: Dict) -> int:
    """Operations of one row of logits."""
    return 2 * m["d_model"] * m["vocab"]


def decode_flops(m: Dict, lens: Sequence[int]) -> int:
    """Operations of one decode step over the real lanes with valid
    lengths ``lens``: each lane's token through every layer, its
    attention over its live context, and its row of logits."""
    per_layer = sum(layer_matmul_flops(m) + 4 * int(n) * m["heads"]
                    * m["head_dim"] for n in lens)
    return per_layer * m["layers"] + len(lens) * head_flops(m)


def prefill_flops(m: Dict, cached: int, total: int) -> int:
    """Operations of prefilling prompt positions ``cached .. total - 1``
    (the cached ones are not computed): each through every layer,
    attending causally over all earlier positions, and one row of logits
    for the last."""
    n = total - cached
    if n <= 0:
        return 0
    # sum over positions t of (t + 1) keys
    keys = (total * (total + 1) - cached * (cached + 1)) // 2
    per_layer = n * layer_matmul_flops(m) + 4 * keys * m["heads"] * m[
        "head_dim"]
    return per_layer * m["layers"] + head_flops(m)
