"""Fused Pallas decode-attention step: rope + KV scatter + attend, one launch.

The unfused decode path (``models/layers.attention_block``, decode branch)
runs four separate ops per step — rotary application, KV scatter-write,
(de)quantization, and masked attention — each streaming the KV cache or the
new token from HBM.  This kernel fuses them into ONE launch with ONE pass
over the cache (grid = batch rows; each program owns its row's cache block):

  * rotary rotation of q and the new k (angles precomputed outside — they
    are O(B * D) and identical math for all three rope variants once
    ``cos``/``sin`` are given; see ``models.layers.rope_cos_sin``),
  * optional int8 per-vector quantization of the new k/v token,
  * scatter-write at the row's own position (``len`` or ``len % slots``
    for ring-buffered local layers),
  * causal/window masking + softmax + attention over the row, one qk dot
    and one p@v dot per KV head.

The kernel avoids every *algorithmic* source of divergence from the
unfused path: the qk scores are per-element d-dots, the softmax runs once
over the full-length score vector (masked entries underflow to exactly
0.0), and the p@v contraction is one full-length dot in the same dtypes.
What remains is the *compiler*: fused and unfused are two separately
compiled graphs, which may contract FMAs or tile reductions differently.
The enforced contract (docs/kernels.md, ``tests/test_pallas_decode.py``)
is: the v-cache write (a pure copy) bit-exact everywhere, k-cache and
attention out bit-exact on the small test shapes and within a few
f32 ULP (rtol=3e-6) on the others, greedy tokens identical at the engine
level.  ``interpret=True`` (the default off-TPU) runs the same kernel body
on CPU CI; on TPU it lowers through Mosaic.

Mosaic shapes the layout: the per-row lengths arrive by scalar prefetch
(SMEM), the angles are widened to the full head dim so every block's last
two dimensions equal the array's, the rotate-half is two lane rolls, and
the caches are copied into their output blocks before the new token is
written — Pallas writes output blocks back to HBM but never reads them
from it.  A row's whole cache is one VMEM block; :func:`vmem_bytes` sizes
it and the wrapper refuses a cache that does not fit
:data:`VMEM_LIMIT_BYTES`.

The helper ``_quantize`` mirrors ``models.layers.quantize_kv`` op-for-op —
they must stay bit-identical, and the test suite pins the pairing.  It is
duplicated rather than imported because ``models.layers`` imports this
module for the ``fused=`` path (the import may not be circular).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Matches models.layers.NEG_INF — the mask fill value of the unfused path.
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: Scoped VMEM the kernel asks Mosaic for (a v5e TensorCore has 128 MiB).
VMEM_LIMIT_BYTES = 64 * 2**20


def default_interpret() -> bool:
    """Interpret-mode default: run the kernel body off-TPU (CPU CI)."""
    return jax.default_backend() != "tpu"


def _tile_bytes(shape, dtype) -> int:
    """VMEM bytes of one block: the last two dims pad to the TPU tile."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // item)              # 8 sublanes of 32 bits, packed
    *lead, rows, cols = shape
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-cols // 128) * 128 * item)


def vmem_bytes(h: int, kh: int, d: int, slots: int, dtype,
               cache_dtype=None, quant: bool = False) -> int:
    """VMEM one grid step holds: every block double-buffered, plus scores.

    The caches count twice over (input and output block); the score,
    probability and mask temporaries are (H, 1, slots) f32, and a quantized
    cache adds its dequantized copy.
    """
    cache_dtype = cache_dtype or dtype
    blocks = (2 * _tile_bytes((h, d), dtype)            # q in, out
              + 2 * _tile_bytes((kh, d), dtype)         # new k, v
              + 2 * _tile_bytes((1, d), jnp.float32)    # cos, sin
              + 4 * _tile_bytes((slots, kh, d), cache_dtype))
    temps = 4 * _tile_bytes((h, 1, slots), jnp.float32)
    if quant:
        blocks += 4 * _tile_bytes((slots, kh, 1), jnp.float32)
        temps += 2 * _tile_bytes((slots, kh, d), dtype)
    return 2 * blocks + temps


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
          half: int) -> jax.Array:
    """models.layers.apply_rope for one row: x (H, D); cos/sin (1, D) f32.

    ``cos``/``sin`` are the angles widened to the head dim by
    :func:`_full_width`: lane ``j < 2*half`` pairs with lane ``j ± half``.
    The rotate-half partner comes from two lane rolls, and each half is the
    unfused expression (``x1*cos - x2*sin``, ``x2*cos + x1*sin``) written
    the same way, so a compiler contracts both paths alike; lanes past
    ``2*half`` pass through untouched.
    """
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, xf.shape, xf.ndim - 1)
    first = lane < half
    partner = jnp.where(first,
                        pltpu.roll(xf, d - half, xf.ndim - 1),   # x[j + half]
                        pltpu.roll(xf, half, xf.ndim - 1))       # x[j - half]
    rotated = jnp.where(first, xf * cos - partner * sin,
                        xf * cos + partner * sin)
    return jnp.where(lane < 2 * half, rotated, xf).astype(x.dtype)


def _full_width(cos: jax.Array, sin: jax.Array, b: int, d: int):
    """(B, ..., W) angles -> (B, 1, D) f32 lane tables for :func:`_rope`."""
    w = cos.shape[-1]
    cos = cos.astype(jnp.float32).reshape(b, 1, w)
    sin = sin.astype(jnp.float32).reshape(b, 1, w)
    ones = jnp.ones((b, 1, d - 2 * w), jnp.float32)
    zeros = jnp.zeros((b, 1, d - 2 * w), jnp.float32)
    return (jnp.concatenate([cos, cos, ones], axis=-1),
            jnp.concatenate([sin, sin, zeros], axis=-1))


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """models.layers.quantize_kv, op-for-op (int8 + f32 per-vector scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _decode_kernel(lens_ref, q_ref, kn_ref, vn_ref, cos_ref, sin_ref, *refs,
                   quant: bool, is_ring: bool, window: int, half: int):
    """One batch row: copy-in -> rope -> (quantize) -> scatter -> attend."""
    if quant:
        (ki_ref, vi_ref, ksi_ref, vsi_ref,
         o_ref, kc_ref, vc_ref, ks_ref, vs_ref) = refs
        pairs = ((ki_ref, kc_ref), (vi_ref, vc_ref),
                 (ksi_ref, ks_ref), (vsi_ref, vs_ref))
    else:
        ki_ref, vi_ref, o_ref, kc_ref, vc_ref = refs
        ks_ref = vs_ref = None
        pairs = ((ki_ref, kc_ref), (vi_ref, vc_ref))
    for src, dst in pairs:
        dst[...] = src[...]

    slots = kc_ref.shape[1]
    idx = lens_ref[pl.program_id(0)]                 # pre-write length
    write = jax.lax.rem(idx, slots) if is_ring else idx

    cos = cos_ref[0]                                 # (1, D) f32
    sin = sin_ref[0]
    q = _rope(q_ref[0, 0], cos, sin, half)           # (H, D)
    k_new = _rope(kn_ref[0, 0], cos, sin, half)      # (K, D)
    v_new = vn_ref[0, 0]                             # v is unroped

    if quant:
        kq, ksc = _quantize(k_new)
        vq, vsc = _quantize(v_new)
        kc_ref[0, pl.ds(write, 1)] = kq[None]
        vc_ref[0, pl.ds(write, 1)] = vq[None]
        ks_ref[0, pl.ds(write, 1)] = ksc[None]
        vs_ref[0, pl.ds(write, 1)] = vsc[None]
    else:
        kc_ref[0, pl.ds(write, 1)] = k_new[None].astype(kc_ref.dtype)
        vc_ref[0, pl.ds(write, 1)] = v_new[None].astype(vc_ref.dtype)

    h, d = q.shape
    kh = k_new.shape[0]
    qg = q.reshape(1, kh, h // kh, d)                # K-major head groups
    k_all, v_all = kc_ref[0], vc_ref[0]              # (slots, K, D)
    if quant:
        k_all = (k_all.astype(jnp.float32) * ks_ref[0]).astype(q.dtype)
        v_all = (v_all.astype(jnp.float32) * vs_ref[0]).astype(q.dtype)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k_all,
                   preferred_element_type=jnp.float32) / math.sqrt(d)

    # Identical mask algebra to the unfused decode_attention (windowed
    # non-ring caches mask here; ring caches pass window=0 — every
    # resident slot is in-window by construction).
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    mask = pos < idx + 1
    if window:
        mask &= pos > idx - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)                   # ONE full-length softmax
    out = jnp.einsum("kgqs,skd->qkgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    o_ref[0] = out.reshape(1, h, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "is_ring", "interpret"))
def fused_decode_attention(
    q: jax.Array,            # (B, 1, H, D) — pre-rope query
    k_new: jax.Array,        # (B, 1, K, D) — pre-rope new key
    v_new: jax.Array,        # (B, 1, K, D)
    k_cache: jax.Array,      # (B, S, K, D)  [int8 when quantized]
    v_cache: jax.Array,      # (B, S, K, D)
    cache_len: jax.Array,    # (B,) int32 pre-write lengths (token count)
    cos: jax.Array,          # (B, ..., W) f32 rope angles (W = rot_dim/2)
    sin: jax.Array,
    k_scale: jax.Array | None = None,   # (B, S, K, 1) f32 when quantized
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,         # non-ring sliding-window mask (0 = causal only)
    is_ring: bool = False,   # ring-buffer write at len % slots
    interpret: bool | None = None,
):
    """One fused decode-attention step; returns ``(out, new caches...)``.

    Plain caches return ``(out, k_cache, v_cache)``; quantized caches
    (``k_scale is not None``) also return the updated scales.  Semantics
    match the unfused ``models.layers.attention_block`` decode branch
    within the numerics contract in the module docstring.  Raises
    ``ValueError`` when a row's cache block does not fit
    :data:`VMEM_LIMIT_BYTES`.
    """
    b, _, h, d = q.shape
    slots = k_cache.shape[1]
    kh = k_new.shape[2]
    if h % kh:
        raise ValueError(f"num_heads ({h}) must divide kv heads ({kh})")
    quant = k_scale is not None
    need = vmem_bytes(h, kh, d, slots, q.dtype, k_cache.dtype, quant)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused decode attention holds a whole cache row in VMEM: "
            f"{need} bytes at {slots} slots exceed VMEM_LIMIT_BYTES "
            f"({VMEM_LIMIT_BYTES}); serve this max_len unfused")
    if interpret is None:
        interpret = default_interpret()

    lens = jnp.asarray(cache_len, jnp.int32)
    if lens.ndim == 0:
        lens = jnp.broadcast_to(lens, (b,))
    half = cos.shape[-1]
    cos_w, sin_w = _full_width(cos, sin, b, d)

    def spec(*block):
        return pl.BlockSpec(block, lambda i, lens: (i,) + (0,) * (
            len(block) - 1))

    tok, qspec = spec(1, 1, kh, d), spec(1, 1, h, d)
    cache, scale, ang = spec(1, slots, kh, d), spec(1, slots, kh, 1), \
        spec(1, 1, d)
    in_specs = [qspec, tok, tok, ang, ang, cache, cache]
    inputs = [q, k_new, v_new, cos_w, sin_w, k_cache, v_cache]
    out_specs = [qspec, cache, cache]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    # Operand numbering counts the scalar-prefetched lengths as operand 0.
    aliases = {6: 1, 7: 2}
    if quant:
        in_specs += [scale, scale]
        inputs += [k_scale, v_scale]
        out_specs += [scale, scale]
        out_shape += [jax.ShapeDtypeStruct(k_scale.shape, jnp.float32),
                      jax.ShapeDtypeStruct(v_scale.shape, jnp.float32)]
        aliases.update({8: 3, 9: 4})

    kernel = functools.partial(_decode_kernel, quant=quant, is_ring=is_ring,
                               window=int(window), half=half)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,), in_specs=in_specs,
            out_specs=tuple(out_specs)),
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_decode_attention",
    )(lens, *inputs)


__all__ = ["fused_decode_attention", "default_interpret", "vmem_bytes",
           "NEG_INF", "VMEM_LIMIT_BYTES"]
