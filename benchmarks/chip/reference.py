"""Plain float32 reference of the served decoders, and its fp8 control.

The reference is written from the configuration file alone and imports
nothing of the program.  It draws the same random weights the program
serves: the same key (0), the same splits and the same draws, rounded to
the configuration's dtype, then computes in float32 at the highest matmul
precision.  A full forward over each prompt with its served tokens, no
cache and no batching of slots.

What it computes, per layer (dense, pre-norm, gated MLP, GQA):

    a = rms(h);  q, k, v = a Wq, a Wk, a Wv;  rope(q), rope(k)
    h = h + softmax(q k^T / sqrt(hd), causal) v Wo
    f = rms(h);  h = h + (silu(f Wg) * (f Wi)) Wo2
    logits = rms(h) Whead[:, :vocab]

with every norm weight zero-initialised, ``rms(x) = x / sqrt(mean(x^2) +
eps) * (1 + w)``.  ``rope_variant`` "half" rotates the first half of the
head dims (ChatGLM), "full" all of them; rotated dims are paired as halves.

It runs one layer at a time: the layer's weights are drawn, used for every
row and dropped, so float32 chatglm3-6b (25 GB) needs about one layer's
0.8 GB at a time.

``precision="fp8"`` is the control: the same computation with every
weight matrix rounded to float8 e4m3 with one scale per output column and
activations fed to the matmuls in bfloat16, the step below the bfloat16
the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from work import Dims

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------------------- #
# Weights: the program's draws, layer by layer
# --------------------------------------------------------------------------- #
def _normal(key, shape, scale=None, div=None):
    """Standard-normal float32 draws kept apart from the scale that
    follows, as the program draws them, so both round alike."""
    x = jax.random.normal(key, shape)
    if div is None:
        return jax.lax.optimization_barrier(x) * scale
    x, div = jax.lax.optimization_barrier((x, jnp.float32(div)))
    return x / div


def _root_keys(m: Dims):
    # One pattern position ("attn"), no tail blocks, four spare keys.
    return jax.random.split(jax.random.key(0), 1 + 4)


@functools.partial(jax.jit, static_argnums=(0,))
def _draw_layer(m: Dims, index):
    keys = _root_keys(m)
    layer_key = jax.random.split(keys[0], m.layers)[index]
    k_attn, k_mlp = jax.random.split(layer_key)
    dt = jnp.dtype(m.dtype)
    hd, d = m.head_dim, m.d
    s, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(m.heads * hd)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {
        "wq": _normal(ka[0], (d, m.heads * hd), s).astype(dt),
        "wk": _normal(ka[1], (d, m.kv_heads * hd), s).astype(dt),
        "wv": _normal(ka[2], (d, m.kv_heads * hd), s).astype(dt),
        "wo": _normal(ka[3], (m.heads * hd, d), so).astype(dt),
        "w_in": _normal(km[0], (d, m.ff), s).astype(dt),
        "w_out": _normal(km[1], (m.ff, d), 1.0 / math.sqrt(m.ff)).astype(dt),
        "w_gate": _normal(km[2], (d, m.ff), s).astype(dt),
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _draw_embed(m: Dims):
    key = _root_keys(m)[-1]
    return _normal(key, (m.vocab_padded, m.d), 0.02).astype(jnp.dtype(m.dtype))


@functools.partial(jax.jit, static_argnums=(0,))
def _draw_head(m: Dims):
    key = _root_keys(m)[-3]
    w = _normal(key, (m.d, m.vocab_padded), div=math.sqrt(m.d))
    return w.astype(jnp.dtype(m.dtype))[:, :m.vocab]


def draw_weights(m: Dims) -> dict:
    """Every weight at once, as the program holds it (tests, small sizes)."""
    return {"embed": _draw_embed(m), "head": _draw_head(m),
            "layers": [_draw_layer(m, i) for i in range(m.layers)]}


# --------------------------------------------------------------------------- #
# Arithmetic
# --------------------------------------------------------------------------- #
def _fp8(w):
    """Round a weight matrix to e4m3 with one scale per output column."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                        1e-12) / F8_MAX
    return (w / scale).astype(F8), scale


def _matmul(x, w, precision):
    if precision == "fp8":
        q, scale = _fp8(w)
        y = jnp.einsum("...i,ij->...j", x.astype(jnp.bfloat16),
                       q.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return y * scale[0]
    return jnp.einsum("...i,ij->...j", x, w.astype(jnp.float32),
                      precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, m: Dims):
    """x (S, H, hd); rotate the leading dims, paired as halves."""
    rot = m.head_dim // 2 if m.rope_variant == "half" else m.head_dim
    half = rot // 2
    inv = 1.0 / (m.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attend(q, k, v, m: Dims):
    """Causal GQA attention of one row: q (S, H, hd), k/v (S, K, hd)."""
    s = q.shape[0]
    g = m.heads // m.kv_heads
    qg = q.reshape(s, m.kv_heads, g, m.head_dim)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k,
                        precision=HIGHEST) / math.sqrt(m.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    return out.reshape(s, m.heads * m.head_dim)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(m: Dims, h, w, precision):
    """One decoder layer over rows of hidden states h (R, S, d), float32."""
    pos = jnp.arange(h.shape[1])

    def row(x):
        a = _rms(x, m.eps)
        q = _matmul(a, w["wq"], precision).reshape(-1, m.heads, m.head_dim)
        k = _matmul(a, w["wk"], precision).reshape(-1, m.kv_heads,
                                                   m.head_dim)
        v = _matmul(a, w["wv"], precision).reshape(-1, m.kv_heads,
                                                   m.head_dim)
        att = _attend(_rope(q, pos, m), _rope(k, pos, m), v, m)
        x = x + _matmul(att, w["wo"], precision)
        f = _rms(x, m.eps)
        mlp = jax.nn.silu(_matmul(f, w["w_gate"], precision)) \
            * _matmul(f, w["w_in"], precision)
        return x + _matmul(mlp, w["w_out"], precision)

    return jax.lax.map(row, h)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(m: Dims, h, read_at, head, precision):
    """Logits (R, G, vocab) at positions ``read_at`` (R, G) of each row."""
    hs = jnp.take_along_axis(h, read_at[..., None], axis=1)
    return _matmul(_rms(hs, m.eps), head, precision)


@jax.jit
def _gaps(ref, tokens, mask, other=None):
    """How far each token's reference logit lies below the reference's best
    at that position; padding reads 0.  ``other`` (control logits) replaces
    the served tokens by its own first choice."""
    if other is not None:
        tokens = jnp.argmax(other, axis=-1)
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


def forward_logits(m: Dims, seqs, read_at, precisions=("f32",)):
    """Logits at ``read_at`` for each precision; one pass over the layers.

    ``seqs`` (R, S) int32 token rows (padding after the real tokens is
    harmless: attention is causal); ``read_at`` (R, G) int32 positions.
    Returns {precision: (R, G, vocab) float32 array}.
    """
    seqs = jnp.asarray(seqs, jnp.int32)
    read_at = jnp.asarray(read_at, jnp.int32)
    embed = _draw_embed(m)
    h0 = jnp.take(embed, seqs, axis=0).astype(jnp.float32)
    del embed
    hs = {p: h0 for p in precisions}
    for i in range(m.layers):
        w = _draw_layer(m, i)
        hs = {p: _layer(m, h, w, p) for p, h in hs.items()}
        del w
    head = _draw_head(m)
    return {p: _logits(m, h, read_at, head, p) for p, h in hs.items()}


def served_rows(reqs, rows=None, seq_len=None, g_max=None):
    """Token rows, read positions, served tokens and mask of requests that
    carry ``prompt_len``, ``tokens`` and ``generated``.

    Row r holds prompt + served tokens but the last; position
    ``prompt_len - 1 + k`` predicts served token ``k``.  ``rows``,
    ``seq_len`` and ``g_max`` pad to a fixed shape, so that one compiled
    program serves every seed.
    """
    n = max(len(reqs), rows or 0)
    seq_len = max([seq_len or 0]
                  + [r.prompt_len + r.gen_len - 1 for r in reqs])
    g_max = max([g_max or 0] + [r.gen_len for r in reqs])
    seqs = np.zeros((n, seq_len), np.int32)
    read_at = np.zeros((n, g_max), np.int32)
    served = np.zeros((n, g_max), np.int32)
    mask = np.zeros((n, g_max), bool)
    for i, r in enumerate(reqs):
        gen = np.asarray(r.generated, np.int32)
        row = np.concatenate([np.asarray(r.tokens, np.int32), gen[:-1]])
        seqs[i, :row.size] = row
        g = gen.size
        read_at[i, :g] = r.prompt_len - 1 + np.arange(g)
        served[i, :g] = gen
        mask[i, :g] = True
    return seqs, read_at, served, mask


def widest_gaps(m: Dims, reqs, *, control: bool = False,
                shape: tuple = (None, None, None)) -> dict:
    """The widest gap of the served tokens (``"served"``) and, with
    ``control``, of the fp8 control's first choices (``"control"``), both
    read against the float32 reference, in logits.  ``shape`` is
    ``(rows, seq_len, g_max)`` to pad to."""
    seqs, read_at, served, mask = served_rows(reqs, *shape)
    precisions = ("f32", "fp8") if control else ("f32",)
    logits = forward_logits(m, seqs, read_at, precisions)
    ref = logits["f32"]
    out = {"served": float(_gaps(ref, jnp.asarray(served),
                                 jnp.asarray(mask)).max()),
           "tokens": int(mask.sum())}
    if control:
        out["control"] = float(_gaps(ref, jnp.asarray(served),
                                     jnp.asarray(mask),
                                     logits["fp8"]).max())
    return out
