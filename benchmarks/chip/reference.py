"""What every model description's float32 reference shares: the
program's draw recipe, the matmul at the highest precision and its fp8
control, and the comparison of served tokens with the reference's logits.

A description (``models/<name>.py``) writes its own forward pass from the
configuration file alone, importing nothing of the program, and hands it
to :func:`widest_gaps`.  The forward pass computes in float32 at the
highest matmul precision; ``precision="fp8"`` is the control: the same
computation with every weight matrix rounded to float8 e4m3 with one scale
per output column and activations fed to the matmuls in bfloat16, the
step below the bfloat16 the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------------------- #
# Weights: the program's draw recipe
# --------------------------------------------------------------------------- #
def _normal(key, shape, scale=None, div=None):
    """Standard-normal float32 draws kept apart from the scale that
    follows, as the program draws them, so both round alike."""
    x = jax.random.normal(key, shape)
    if div is None:
        return jax.lax.optimization_barrier(x) * scale
    x, div = jax.lax.optimization_barrier((x, jnp.float32(div)))
    return x / div


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


def widest_gaps(forward_logits, m, reqs, *, control: bool = False,
                shape: tuple = (None, None, None)) -> dict:
    """The widest gap of the served tokens (``"served"``) and, with
    ``control``, of the fp8 control's first choices (``"control"``), both
    read against the float32 reference, in logits.

    ``forward_logits(m, seqs, read_at, precisions)`` is a description's
    forward pass (``{precision: (R, G, vocab) logits}``); ``shape`` is
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
