"""Fused Pallas decode-attention step vs the unfused decode branch.

Numerics contract (docs/kernels.md):

  * **Small shapes** (the six parametrized cases below, up to 64 slots):
    the fused kernel is *bit-exact* against the unfused path — the qk
    scores, the one-shot softmax, and the one-einsum p@v see identical
    inputs in identical order, and interpret mode runs the same XLA ops.
    The cases (plain / bf16 / quant / ring / window / quant+ring, all with
    mixed per-slot lengths) assert exact equality.
  * **Larger shapes**: the fused and unfused paths are two separately
    compiled XLA graphs, and XLA:CPU may contract FMAs / tile reductions
    differently per graph — so the contract is: v-cache bit-exact (pure
    copy, no arithmetic), k-cache and attention out within a few f32 ULP
    (``rtol=3e-6, atol=1e-6``).  Greedy tokens stay bit-identical at the
    engine level (argmax absorbs ULP noise) — asserted by the
    ``decode_attn_token_identity`` smoke-gate record.

Also here: the grouped-GQA einsum regression (the old ``repeat_kv``
materialization, inlined below as the oracle) for both
``decode_attention`` and ``chunked_attention``, and block-level
``attention_block(fused=True)`` equivalence for quant and ring configs.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # no hypothesis: seeded-sampling shim, not a skip
    from proptest_fallback import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.decode_attention import fused_decode_attention
from repro.models.config import ModelConfig
from repro.models.layers import (NEG_INF, NO_SHARD, apply_rope,
                                 attention_block, chunked_attention,
                                 decode_attention, dequantize_kv, quantize_kv,
                                 repeat_kv, rope_cos_sin)

CFG_HALF = get_config("chatglm3-6b")     # rope_variant=half
CFG_STD = get_config("granite-3-8b")     # rope_variant=full


# --------------------------------------------------------------------------- #
# Unfused reference: models.layers.attention_block decode branch, post-proj
# --------------------------------------------------------------------------- #
def _unfused_step(q, k, v, kc, vc, ks, vs, idx, cfg, *, window=0,
                  is_ring=False):
    b = q.shape[0]
    positions = idx[:, None]
    k = apply_rope(k, positions, cfg)
    q = apply_rope(q, positions, cfg)
    slots = kc.shape[1]
    quant = ks is not None
    write = jax.lax.rem(idx, slots) if is_ring else idx
    rows = jnp.arange(b)
    if quant:
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        kc = kc.at[rows, write].set(kq[:, 0])
        vc = vc.at[rows, write].set(vq[:, 0])
        ks = ks.at[rows, write].set(ksc[:, 0].astype(jnp.float32))
        vs = vs.at[rows, write].set(vsc[:, 0].astype(jnp.float32))
        k_use = dequantize_kv(kc, ks, q.dtype)
        v_use = dequantize_kv(vc, vs, q.dtype)
    else:
        kc = kc.at[rows, write].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[rows, write].set(v[:, 0].astype(vc.dtype))
        k_use, v_use = kc, vc
    out = decode_attention(q, k_use, v_use, idx + 1,
                           window=0 if is_ring else window)
    return out, kc, vc, ks, vs


def _make_case(cfg, b, s, h, kh, d, dtype, lens, quant, is_ring, window,
               seed=0):
    keys = jax.random.split(jax.random.key(seed), 8)
    q = jax.random.normal(keys[0], (b, 1, h, d), dtype)
    k = jax.random.normal(keys[1], (b, 1, kh, d), dtype)
    v = jax.random.normal(keys[2], (b, 1, kh, d), dtype)
    if quant:
        kc = jax.random.randint(keys[3], (b, s, kh, d), -127, 128, jnp.int8)
        vc = jax.random.randint(keys[4], (b, s, kh, d), -127, 128, jnp.int8)
        ks = jax.random.uniform(keys[5], (b, s, kh, 1), jnp.float32,
                                0.001, 0.1)
        vs = jax.random.uniform(keys[6], (b, s, kh, 1), jnp.float32,
                                0.001, 0.1)
    else:
        kc = jax.random.normal(keys[3], (b, s, kh, d), dtype)
        vc = jax.random.normal(keys[4], (b, s, kh, d), dtype)
        ks = vs = None
    idx = jnp.asarray(lens, jnp.int32)

    ref = jax.jit(functools.partial(_unfused_step, cfg=cfg, window=window,
                                    is_ring=is_ring))(
        q, k, v, kc, vc, ks, vs, idx)
    cos, sin = rope_cos_sin(idx[:, None], d, cfg)
    got = fused_decode_attention(q, k, v, kc, vc, idx, cos, sin, ks, vs,
                                 window=0 if is_ring else window,
                                 is_ring=is_ring, interpret=True)
    if quant:
        go, gkc, gvc, gks, gvs = got
    else:
        (go, gkc, gvc), gks, gvs = got, None, None
    ro, rkc, rvc, rks, rvs = ref
    return {"out": (go, ro), "kc": (gkc, rkc), "vc": (gvc, rvc),
            "ks": (gks, rks), "vs": (gvs, rvs)}


# (name, cfg, B, S, H, K, D, dtype, lens, quant, is_ring, window) — all
# small shapes (S <= 64), where exact equality holds.
EXACT_CASES = [
    ("plain-half-rope", CFG_HALF, 3, 64, 8, 2, 16, jnp.float32,
     [5, 0, 63], False, False, 0),
    ("plain-std-rope-bf16", CFG_STD, 2, 32, 4, 4, 8, jnp.bfloat16,
     [7, 31], False, False, 0),
    ("quant", CFG_HALF, 3, 64, 8, 2, 16, jnp.float32,
     [5, 0, 63], True, False, 0),
    ("ring", CFG_HALF, 3, 32, 8, 2, 16, jnp.float32,
     [100, 3, 32], False, True, 32),
    ("window-nonring", CFG_STD, 2, 64, 4, 4, 8, jnp.float32,
     [40, 10], False, False, 16),
    ("quant-ring", CFG_HALF, 2, 32, 4, 2, 16, jnp.float32,
     [70, 1], True, True, 32),
]


@pytest.mark.parametrize(
    "case", EXACT_CASES, ids=[c[0] for c in EXACT_CASES])
def test_fused_matches_unfused_bitwise_single_chunk(case):
    """Plain / quant / ring / window variants, mixed per-slot lens: every
    output (attention out, caches, scales) is bit-identical on these
    small shapes (see module docstring)."""
    name, cfg, b, s, h, kh, d, dtype, lens, quant, is_ring, window = case
    pairs = _make_case(cfg, b, s, h, kh, d, dtype, lens, quant, is_ring,
                       window)
    for nm, (got, ref) in pairs.items():
        if got is None:
            assert ref is None
            continue
        assert jnp.array_equal(got, ref), (
            f"{name}/{nm}: maxdiff="
            f"{np.max(np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32)))}")


def test_fused_matches_unfused_multi_chunk_ulp():
    """S=128, head_dim 32: v-cache bit-exact, k-cache and
    out within the documented ULP tolerance (separately compiled graphs
    may contract FMAs differently — docs/kernels.md)."""
    pairs = _make_case(CFG_HALF, 4, 128, 8, 2, 32, jnp.float32,
                       [0, 17, 65, 127], False, False, 0)
    got_v, ref_v = pairs["vc"]
    assert jnp.array_equal(got_v, ref_v)  # pure copy: no arithmetic at all
    for nm in ("out", "kc"):
        got, ref = pairs[nm]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-6, atol=1e-6, err_msg=nm)


@given(b=st.integers(min_value=1, max_value=3),
       g=st.integers(min_value=1, max_value=4),
       d=st.sampled_from([8, 16]),
       seed=st.integers(min_value=0, max_value=10**6))
@settings(max_examples=5, deadline=None)
def test_fused_property_random_shapes(b, g, d, seed):
    """Random (batch, GQA group, head_dim, lens) within the ULP contract."""
    kh = 2
    h = kh * g
    s = 128
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, s, size=b).tolist()
    pairs = _make_case(CFG_STD, b, s, h, kh, d, jnp.float32, lens,
                       False, False, 0, seed=seed)
    got_v, ref_v = pairs["vc"]
    assert jnp.array_equal(got_v, ref_v)
    for nm in ("out", "kc"):
        got, ref = pairs[nm]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-6, atol=1e-6, err_msg=nm)


# --------------------------------------------------------------------------- #
# Grouped-GQA einsum regression: the old repeat_kv materialization, inlined
# as the oracle (this was layers.decode_attention before the grouped path)
# --------------------------------------------------------------------------- #
def _decode_attention_repeat_kv(q, k_cache, v_cache, cache_len, *, window=0):
    b, sq, h, d = q.shape
    skv = k_cache.shape[1]
    k = repeat_kv(k_cache, h)
    v = repeat_kv(v_cache, h)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    pos = jnp.arange(skv)
    lens = jnp.asarray(cache_len, jnp.int32)
    if lens.ndim == 0:
        lens = jnp.broadcast_to(lens, (b,))
    mask = pos[None, :] < lens[:, None]
    if window:
        mask &= pos[None, :] > lens[:, None] - 1 - window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@pytest.mark.parametrize("window", [0, 16])
def test_decode_attention_matches_repeat_kv_oracle(window):
    """The grouped (K, H/K) einsum contracts the same per-element d-dots as
    the repeat_kv-materialized path; only the cache traffic changes."""
    b, s, h, kh, d = 3, 64, 8, 2, 16
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (b, 1, h, d), jnp.float32)
    kc = jax.random.normal(keys[1], (b, s, kh, d), jnp.float32)
    vc = jax.random.normal(keys[2], (b, s, kh, d), jnp.float32)
    lens = jnp.asarray([5, 33, 64], jnp.int32)
    got = decode_attention(q, kc, vc, lens, window=window)
    want = _decode_attention_repeat_kv(q, kc, vc, lens, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=1e-6)


def test_chunked_attention_matches_repeat_kv_oracle():
    """Prefill path: grouped online-softmax attention vs a naive full
    repeat_kv softmax (looser tolerance — the online rescaling
    re-associates the sum by construction)."""
    b, s, h, kh, d = 2, 48, 8, 2, 16
    keys = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, kh, d), jnp.float32)
    kf = repeat_kv(k, h)
    vf = repeat_kv(v, h)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, kf,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    want = jnp.einsum("bhqs,bshd->bqhd", p.astype(vf.dtype), vf,
                      preferred_element_type=jnp.float32).astype(q.dtype)
    got = chunked_attention(q, k, v, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# Block level: attention_block(fused=True) vs fused=False, quant and ring
# --------------------------------------------------------------------------- #
def _tiny_cfg(h, kh, d, **kw):
    return ModelConfig(name="tiny", family="dense", num_layers=1,
                       d_model=h * d, d_ff=4 * h * d, vocab_size=64,
                       num_heads=h, num_kv_heads=kh, head_dim=d,
                       rope_variant="half", **kw)


def _block_case(*, quant, window, slots, h=4, kh=2, d=16, b=3):
    cfg = _tiny_cfg(h, kh, d, sliding_window=window)
    dm = cfg.d_model
    keys = jax.random.split(jax.random.key(3), 6)
    p = {"wq": jax.random.normal(keys[0], (dm, h * d), jnp.float32) * 0.1,
         "wk": jax.random.normal(keys[1], (dm, kh * d), jnp.float32) * 0.1,
         "wv": jax.random.normal(keys[2], (dm, kh * d), jnp.float32) * 0.1,
         "wo": jax.random.normal(keys[3], (h * d, dm), jnp.float32) * 0.1}
    x = jax.random.normal(keys[4], (b, 1, dm), jnp.float32)
    idx = jnp.asarray([1, 7, slots - 1], jnp.int32)[:b]
    cdtype = jnp.int8 if quant else jnp.float32
    cache = {
        "k": jax.random.normal(keys[5], (b, slots, kh, d)).astype(cdtype),
        "v": jax.random.normal(keys[5], (b, slots, kh, d)).astype(cdtype),
        "len": idx,
    }
    if quant:
        cache["k_scale"] = jnp.full((b, slots, kh, 1), 0.02, jnp.float32)
        cache["v_scale"] = jnp.full((b, slots, kh, 1), 0.02, jnp.float32)
    run = functools.partial(attention_block, x, p, cfg, NO_SHARD,
                            positions=idx[:, None], window=window,
                            cache=cache)
    return run(fused=False), run(fused=True)


@pytest.mark.parametrize("quant,window,slots", [
    (False, 0, 32),       # plain causal
    (False, 32, 32),      # ring buffer (slots == window)
    (True, 0, 32),        # int8 KV quant
], ids=["plain", "ring", "quant"])
def test_attention_block_fused_flag_equivalence(quant, window, slots):
    """attention_block(fused=True) reproduces the unfused decode branch end
    to end (projections included) on small shapes."""
    (y_ref, c_ref), (y_got, c_got) = _block_case(quant=quant, window=window,
                                                 slots=slots)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_ref),
                               rtol=3e-6, atol=1e-6)
    assert jnp.array_equal(c_got["len"], c_ref["len"])
    for nm in c_ref:
        if nm == "len":
            continue
        got, ref = np.asarray(c_got[nm]), np.asarray(c_ref[nm])
        if got.dtype == np.int8:
            # One quantization step of slack: the projections feeding
            # quantize_kv are compiled in two different graphs, so a value
            # sitting exactly on a rounding boundary may flip by 1.
            diff = np.abs(got.astype(np.int32) - ref.astype(np.int32))
            assert diff.max() <= 1 and (diff != 0).mean() < 0.01, nm
        else:
            np.testing.assert_allclose(got, ref, rtol=3e-6, atol=1e-6,
                                       err_msg=nm)


def test_fused_decode_refuses_a_cache_row_past_vmem():
    """A row's whole cache is one VMEM block (ROADMAP A6): past the budget
    the wrapper names the limit instead of handing Mosaic a kernel that
    cannot fit."""
    from repro.kernels.decode_attention import VMEM_LIMIT_BYTES, vmem_bytes
    b, s, h, kh, d = 1, 8192, 32, 2, 128
    assert vmem_bytes(h, kh, d, s, jnp.bfloat16) > VMEM_LIMIT_BYTES
    sds = jax.ShapeDtypeStruct
    args = (sds((b, 1, h, d), jnp.bfloat16), sds((b, 1, kh, d), jnp.bfloat16),
            sds((b, 1, kh, d), jnp.bfloat16), sds((b, s, kh, d), jnp.bfloat16),
            sds((b, s, kh, d), jnp.bfloat16), sds((b,), jnp.int32),
            sds((b, 1, d // 4), jnp.float32), sds((b, 1, d // 4), jnp.float32))
    with pytest.raises(ValueError, match="VMEM_LIMIT_BYTES"):
        jax.eval_shape(lambda *a: fused_decode_attention(*a), *args)
