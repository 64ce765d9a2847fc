"""Model description of a dense, pre-norm, gated-MLP, GQA decoder.

A configuration file names its description by ``"description"``; the
harness loads ``models/<name>.py`` (``work.description``) and reaches the
model only through it.  A description provides:

  * ``dims(cfg)``: the sizes the counts and the reference need, read from
    the file's published keys, as a frozen (hashable) record with at least
    ``chips``, ``vocab`` and ``dtype``;
  * ``program_dims(program, chips)``: the same record read from the
    program's ``ModelConfig`` (the scaled-down model of the CPU tests
    takes its sizes from there), and ``program_mismatch(program, m)``:
    ``{field: (program's, file's)}`` of each field in which they differ;
  * the work counts, from shapes alone: ``param_count``,
    ``kv_bytes_per_token``, ``prefill_flops``, ``request_flops``,
    ``decode_step_bytes(m, rows, live)``, ``prefill_step_bytes(m, rows,
    prompt)``;
  * the plain float32 reference and its fp8 control:
    ``draw_weights(m)`` and ``widest_gaps(m, reqs, *, shape, control)``.

This one covers chatglm3-6b and granite-3-8b.  Bytes are those a chip
holds or must read; FLOPs count multiply and add as two.  Per-chip figures
divide by the chips a layer is split over: the tensor-parallel layout
splits every matrix, the embedding, the output head and the cache four
ways, and replicates only the norms.

The reference is written from the configuration file alone and imports
nothing of the program.  It draws the same random weights the program
serves: the same key (0), the same splits and the same draws, rounded to
the configuration's dtype, then computes in float32 at the highest matmul
precision.  A full forward over each prompt with its served tokens, no
cache and no batching of slots.  Per layer:

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
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

import reference
from reference import HIGHEST, _matmul, _normal, _rms
from work import DTYPE_BYTES

#: Published key names of each ``model_type`` this description reads.
_KEYS = {
    "chatglm": dict(layers="num_layers", d="hidden_size",
                    ff="ffn_hidden_size", heads="num_attention_heads",
                    kv_heads="multi_query_group_num", head_dim="kv_channels",
                    vocab="padded_vocab_size", eps="layernorm_epsilon"),
    "granite": dict(layers="num_hidden_layers", d="hidden_size",
                    ff="intermediate_size", heads="num_attention_heads",
                    kv_heads="num_key_value_heads", head_dim=None,
                    vocab="vocab_size", eps="rms_norm_eps"),
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense, gated-MLP, GQA decoder that the counts need."""

    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int            # real vocabulary (logits that are read)
    vocab_padded: int     # rows held by the embedding and the output head
    eps: float
    rope_variant: str     # "half" (ChatGLM) or "full"
    rope_theta: float
    dtype: str
    chips: int

    @property
    def elem_bytes(self) -> int:
        return DTYPE_BYTES[self.dtype]


def dims(cfg: dict) -> Dims:
    keys = _KEYS[cfg["model_type"]]
    d = int(cfg[keys["d"]])
    heads = int(cfg[keys["heads"]])
    head_dim = (int(cfg[keys["head_dim"]]) if keys["head_dim"]
                else d // heads)
    vocab = int(cfg[keys["vocab"]])
    pad = int(cfg.get("vocab_pad_to", 1))
    return Dims(layers=int(cfg[keys["layers"]]), d=d, ff=int(cfg[keys["ff"]]),
                heads=heads, kv_heads=int(cfg[keys["kv_heads"]]),
                head_dim=head_dim, vocab=vocab,
                vocab_padded=-(-vocab // pad) * pad,
                eps=float(cfg[keys["eps"]]),
                rope_variant=cfg["rope_variant"],
                rope_theta=float(cfg["rope_theta"]),
                dtype=cfg.get("served_dtype", cfg["torch_dtype"]),
                chips=int(cfg["chips"]))


def program_dims(program, chips: int = 1) -> Dims:
    """The sizes of the program's ``ModelConfig``, on ``chips``."""
    return Dims(layers=program.num_layers, d=program.d_model,
                ff=program.d_ff, heads=program.num_heads,
                kv_heads=program.num_kv_heads, head_dim=program.qk_head_dim,
                vocab=program.vocab_size, vocab_padded=program.vocab_padded,
                eps=program.norm_eps, rope_variant=program.rope_variant,
                rope_theta=program.rope_theta, dtype=program.dtype,
                chips=chips)


def program_mismatch(program, m: Dims) -> dict:
    """{field: (program's, file's)} of every size that differs."""
    p = program_dims(program, m.chips)
    return {f.name: (getattr(p, f.name), getattr(m, f.name))
            for f in dataclasses.fields(Dims)
            if getattr(p, f.name) != getattr(m, f.name)}


# --------------------------------------------------------------------------- #
# Parameters and cache
# --------------------------------------------------------------------------- #
def layer_params(m: Dims) -> int:
    """Matrix and norm elements of one decoder layer."""
    attn = m.d * m.head_dim * (m.heads + 2 * m.kv_heads) \
        + m.heads * m.head_dim * m.d
    mlp = 3 * m.d * m.ff
    return attn + mlp + 2 * m.d


def param_count(m: Dims) -> int:
    """Every element the chips hold: layers, embedding, head, final norm."""
    return m.layers * layer_params(m) + 2 * m.vocab_padded * m.d + m.d


def param_bytes(m: Dims) -> int:
    """Bytes of all weights, over all chips."""
    return param_count(m) * m.elem_bytes


def kv_bytes_per_token(m: Dims) -> int:
    """Cache bytes one position takes on one chip (keys and values)."""
    return 2 * m.layers * m.kv_heads * m.head_dim * m.elem_bytes // m.chips


def matmul_params(m: Dims) -> int:
    """Weights one token multiplies through: every layer, the output head
    over the real vocabulary.  The embedding is a lookup, not a product."""
    return m.layers * (layer_params(m) - 2 * m.d) + m.d * m.vocab


# --------------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------------- #
def attn_flops(m: Dims, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` positions."""
    return 4 * m.layers * m.heads * m.head_dim * kv_len


def prefill_flops(m: Dims, prompt_len: int) -> int:
    """Model FLOPs of one prompt: 2 per weight per token, causal attention."""
    causal = prompt_len * (prompt_len + 1) // 2
    return 2 * matmul_params(m) * prompt_len \
        + 4 * m.layers * m.heads * m.head_dim * causal


def decode_flops(m: Dims, kv_len: int) -> int:
    """Model FLOPs of one generated token attending ``kv_len`` positions."""
    return 2 * matmul_params(m) + attn_flops(m, kv_len)


def request_flops(m: Dims, prompt_len: int, gen_len: int) -> int:
    """All model FLOPs of one request: its prompt, then ``gen_len - 1``
    decode steps (the first token comes from the prompt's pass), step ``k``
    attending ``prompt_len + k`` positions."""
    steps = gen_len - 1
    kv_sum = steps * prompt_len + steps * (steps + 1) // 2
    return prefill_flops(m, prompt_len) + 2 * matmul_params(m) * steps \
        + 4 * m.layers * m.heads * m.head_dim * kv_sum


def decode_step_bytes(m: Dims, rows: int, live_positions: float) -> float:
    """Least bytes one chip reads in a decode step: every weight except the
    embedding table (``rows`` of it are looked up), plus the live cache."""
    weights = (param_count(m) - m.vocab_padded * m.d) * m.elem_bytes
    lookup = rows * m.d * m.elem_bytes
    return (weights + lookup) / m.chips \
        + kv_bytes_per_token(m) * live_positions


def prefill_step_bytes(m: Dims, rows: int, prompt_len: int) -> float:
    """Least bytes one chip moves in a prefill of ``rows`` prompts: every
    weight read once, the rows' cache written."""
    weights = (param_count(m) - m.vocab_padded * m.d) * m.elem_bytes
    return weights / m.chips + kv_bytes_per_token(m) * rows * prompt_len


# --------------------------------------------------------------------------- #
# Reference weights: the program's draws, layer by layer
# --------------------------------------------------------------------------- #
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
# Reference arithmetic
# --------------------------------------------------------------------------- #
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


def widest_gaps(m: Dims, reqs, *, control: bool = False,
                shape: tuple = (None, None, None)) -> dict:
    """``reference.widest_gaps`` over this description's forward pass."""
    return reference.widest_gaps(forward_logits, m, reqs, control=control,
                                 shape=shape)
