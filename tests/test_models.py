"""Model-layer correctness: oracle equivalences and prefill/decode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (ModelConfig, cross_entropy, decode_step, forward,
                          init_cache, init_params)
from repro.models import layers as L


# --------------------------------------------------------------------------- #
# Attention oracles
# --------------------------------------------------------------------------- #
def naive_attention(q, k, v, *, window=0):
    b, sq, h, d = q.shape
    kh = k.shape[2]
    q4 = q.reshape(b, sq, kh, h // kh, d)
    s = jnp.einsum("bqkrd,bskd->bkrqs", q4, k) / np.sqrt(d)
    i = jnp.arange(sq)[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrqs,bskd->bqkrd", p, v)
    return o.reshape(b, sq, h, d)


@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("kv_chunk", [4, 16, 64])
@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
def test_chunked_attention_matches_naive(window, kv_chunk, gqa):
    h, kh = gqa
    b, s, d = 2, 33, 8  # deliberately not a multiple of kv_chunk
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    got = L.chunked_attention(q, k, v, window=window, kv_chunk=kv_chunk)
    want = naive_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# SSD oracle: naive per-token recurrence
# --------------------------------------------------------------------------- #
def naive_ssd(x, dt_a, bmat, cmat):
    b, t, h, p = x.shape
    n = bmat.shape[-1]
    s = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        da = jnp.exp(dt_a[:, i])                       # (B,H)
        s = da[..., None, None] * s + jnp.einsum(
            "bhp,bn->bhpn", x[:, i], bmat[:, i])
        ys.append(jnp.einsum("bn,bhpn->bhp", cmat[:, i], s))
    return jnp.stack(ys, axis=1), s


@pytest.mark.parametrize("chunk", [2, 4, 8, 16])
def test_ssd_chunked_matches_recurrence(chunk):
    b, t, h, p, n = 2, 16, 3, 4, 5
    ks = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt_a = -jnp.abs(jax.random.normal(ks[1], (b, t, h)))  # decay < 1
    bm = jax.random.normal(ks[2], (b, t, n))
    cm = jax.random.normal(ks[3], (b, t, n))
    y, st = L.ssd_chunked(x, dt_a, bm, cm, chunk=chunk)
    y_ref, st_ref = naive_ssd(x, dt_a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_ssd_init_state_continuation():
    """Processing [part1; part2] == processing part2 with part1's state."""
    b, t, h, p, n = 1, 16, 2, 4, 3
    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt_a = -jnp.abs(jax.random.normal(ks[1], (b, t, h)))
    bm = jax.random.normal(ks[2], (b, t, n))
    cm = jax.random.normal(ks[3], (b, t, n))
    y_full, st_full = L.ssd_chunked(x, dt_a, bm, cm, chunk=4)
    y1, st1 = L.ssd_chunked(x[:, :8], dt_a[:, :8], bm[:, :8], cm[:, :8],
                            chunk=4)
    y2, st2 = L.ssd_chunked(x[:, 8:], dt_a[:, 8:], bm[:, 8:], cm[:, 8:],
                            chunk=4, init_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# Prefill/decode parity — the gold-standard cache test, per family
# --------------------------------------------------------------------------- #
def _parity(cfg, *, s=12, atol=2e-3):
    params = init_params(jax.random.key(0), cfg)
    b = 2
    tokens = jax.random.randint(jax.random.key(3), (b, s), 0, cfg.vocab_size)
    full = forward(params, cfg, tokens=tokens)           # (B,S,V)

    cache = init_cache(cfg, b, max_len=s + 4)
    step = jax.jit(lambda p, t, c, l: decode_step(p, cfg, t, c, l))
    outs = []
    for i in range(s):
        lg, cache = step(params, tokens[:, i:i + 1], cache, jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=1e-3, atol=atol)


@pytest.mark.slow
def test_parity_dense_gqa():
    _parity(ModelConfig(name="d", family="dense", num_layers=3, d_model=32,
                        d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=2,
                        head_dim=8, dtype="float32"))


@pytest.mark.slow
def test_parity_local_global_ring_buffer():
    # window = 4 < seq: exercises the ring-buffer decode path.
    _parity(ModelConfig(name="lg", family="dense", num_layers=4, d_model=32,
                        d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=2,
                        head_dim=8, pattern=("local", "attn"),
                        sliding_window=4, dtype="float32"))


@pytest.mark.slow
def test_parity_half_rope():
    _parity(ModelConfig(name="hr", family="dense", num_layers=2, d_model=32,
                        d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=2,
                        head_dim=8, rope_variant="half", dtype="float32"))


@pytest.mark.slow
def test_parity_mamba():
    _parity(ModelConfig(name="m", family="ssm", num_layers=3, d_model=32,
                        d_ff=0, vocab_size=61, pattern=("mamba",),
                        ssm_state=8, ssm_head_dim=8, ssm_chunk=4,
                        dtype="float32"), atol=5e-3)


@pytest.mark.slow
def test_parity_hybrid_shared_block():
    _parity(ModelConfig(name="h", family="hybrid", num_layers=6, d_model=32,
                        d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=4,
                        head_dim=8, pattern=("mamba", "shared_attn"),
                        ssm_state=8, ssm_head_dim=8, ssm_chunk=4,
                        dtype="float32"), atol=5e-3)


def test_parity_moe():
    # capacity_factor high enough that no token drops (else parity breaks
    # between batched prefill and one-token decode routing).
    _parity(ModelConfig(name="mo", family="moe", num_layers=2, d_model=32,
                        d_ff=16, vocab_size=61, num_heads=4, num_kv_heads=2,
                        head_dim=8, pattern=("attn_moe",), num_experts=4,
                        num_experts_per_tok=2, capacity_factor=4.0,
                        dtype="float32"))


# --------------------------------------------------------------------------- #
# Misc model invariants
# --------------------------------------------------------------------------- #
def test_cross_entropy_uniform_logits():
    v = 32
    logits = jnp.zeros((2, 8, v))
    tgt = jnp.zeros((2, 8), jnp.int32)
    assert float(cross_entropy(logits, tgt)) == pytest.approx(np.log(v),
                                                              rel=1e-5)


def test_causality_future_token_has_no_effect():
    cfg = ModelConfig(name="c", family="dense", num_layers=2, d_model=32,
                      d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=2,
                      head_dim=8, dtype="float32")
    params = init_params(jax.random.key(0), cfg)
    t1 = jax.random.randint(jax.random.key(4), (1, 10), 0, 61)
    t2 = t1.at[0, -1].set((t1[0, -1] + 7) % 61)
    l1 = forward(params, cfg, tokens=t1)
    l2 = forward(params, cfg, tokens=t2)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_param_count_matches_actual_tree():
    cfg = ModelConfig(name="pc", family="moe", num_layers=5, d_model=32,
                      d_ff=16, vocab_size=61, num_heads=4, num_kv_heads=2,
                      head_dim=8, pattern=("attn_moe", "attn"),
                      num_experts=4, num_experts_per_tok=2, dtype="float32")
    params = init_params(jax.random.key(0), cfg)
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert cfg.param_count() == actual


# --------------------------------------------------------------------------- #
# Prefill -> decode continuation parity
# --------------------------------------------------------------------------- #
def _prefill_parity(cfg, s_pre=8, s_dec=4, atol=3e-3):
    from repro.models import prefill
    params = init_params(jax.random.key(0), cfg)
    b = 2
    total = s_pre + s_dec
    tokens = jax.random.randint(jax.random.key(5), (b, total), 0,
                                cfg.vocab_size)
    full = forward(params, cfg, tokens=tokens)

    cache = init_cache(cfg, b, max_len=total + 2)
    lg_pre, cache = prefill(params, cfg, tokens=tokens[:, :s_pre],
                            caches=cache)
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(full[:, :s_pre]),
                               rtol=1e-3, atol=atol)
    step = jax.jit(lambda p, t, c, l: decode_step(p, cfg, t, c, l))
    for i in range(s_pre, total):
        lg, cache = step(params, tokens[:, i:i + 1], cache, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, i]),
                                   rtol=1e-3, atol=atol)


def test_prefill_parity_dense():
    _prefill_parity(ModelConfig(
        name="pp", family="dense", num_layers=3, d_model=32, d_ff=64,
        vocab_size=61, num_heads=4, num_kv_heads=2, head_dim=8,
        dtype="float32"))


def test_prefill_parity_local_ring():
    # window 4, prefill 8 (= 2 windows): ring invariant must hold.
    _prefill_parity(ModelConfig(
        name="ppl", family="dense", num_layers=4, d_model=32, d_ff=64,
        vocab_size=61, num_heads=4, num_kv_heads=2, head_dim=8,
        pattern=("local", "attn"), sliding_window=4, dtype="float32"))


def test_prefill_parity_mamba():
    _prefill_parity(ModelConfig(
        name="ppm", family="ssm", num_layers=3, d_model=32, d_ff=0,
        vocab_size=61, pattern=("mamba",), ssm_state=8, ssm_head_dim=8,
        ssm_chunk=4, dtype="float32"), atol=5e-3)


def test_prefill_parity_hybrid():
    _prefill_parity(ModelConfig(
        name="pph", family="hybrid", num_layers=6, d_model=32, d_ff=64,
        vocab_size=61, num_heads=4, num_kv_heads=4, head_dim=8,
        pattern=("mamba", "shared_attn"), ssm_state=8, ssm_head_dim=8,
        ssm_chunk=4, dtype="float32"), atol=5e-3)


# --------------------------------------------------------------------------- #
# Int8 KV-cache quantization (serving feature, §Perf cell 3)
# --------------------------------------------------------------------------- #
def test_kv_quant_decode_close_to_full_precision():
    import dataclasses
    cfg = ModelConfig(name="kvq", family="dense", num_layers=3, d_model=32,
                      d_ff=64, vocab_size=61, num_heads=4, num_kv_heads=2,
                      head_dim=8, dtype="float32")
    cfg_q = dataclasses.replace(cfg, kv_quant=True)
    params = init_params(jax.random.key(0), cfg)
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.key(3), (b, s), 0, 61)
    full = forward(params, cfg, tokens=tokens)

    cache = init_cache(cfg_q, b, max_len=s + 2)
    assert cache["groups"][0]["k"].dtype == jnp.int8
    step = jax.jit(lambda p, t, c, l: decode_step(p, cfg_q, t, c, l))
    outs = []
    for i in range(s):
        lg, cache = step(params, tokens[:, i:i + 1], cache, jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    err = jnp.max(jnp.abs(dec - full))
    assert float(err) < 0.35, float(err)   # int8 quantization noise only
    # Greedy decisions must agree with full precision.
    agree = jnp.mean((dec.argmax(-1) == full.argmax(-1)).astype(jnp.float32))
    assert float(agree) >= 0.9, float(agree)


def test_kv_quant_roundtrip_accuracy():
    x = jax.random.normal(jax.random.key(0), (4, 16, 2, 8))
    q, s = L.quantize_kv(x)
    back = L.dequantize_kv(q, s, jnp.float32)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(np.abs(x).max()) / 100)


# --------------------------------------------------------------------------- #
# Per-slot decode state (continuous batching, DESIGN.md §6)
# --------------------------------------------------------------------------- #
def test_decode_attention_per_slot_lengths_match_scalar_rows():
    """A (B,) cache_len vector must give each row exactly the result of a
    scalar-length call on that row alone."""
    rng = jax.random.key(7)
    kq, kk, kv = jax.random.split(rng, 3)
    b, skv, h, kvh, d = 3, 16, 4, 2, 8
    q = jax.random.normal(kq, (b, 1, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, skv, kvh, d), jnp.float32)
    lens = [5, 9, 16]
    for window in (0, 6):
        out = L.decode_attention(q, k, v, jnp.asarray(lens, jnp.int32),
                                 window=window)
        for i, ln in enumerate(lens):
            ref = L.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                     jnp.int32(ln), window=window)
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(ref[0]))


def test_decode_step_vector_lens_match_scalar():
    """All-equal vector cache_len must reproduce the scalar path exactly."""
    from repro.configs import get_config
    from repro.models import prefill, scaled_down

    cfg = scaled_down(get_config("chatglm3-6b"))
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 6),
                                          np.int64).astype(np.int32))
    caches = init_cache(cfg, 2, max_len=12)
    _, caches = prefill(params, cfg, caches=caches, tokens=tokens)
    tok = jnp.zeros((2, 1), jnp.int32)
    lg_s, c_s = decode_step(params, cfg, tok, caches, jnp.int32(6))
    lg_v, c_v = decode_step(params, cfg, tok, caches,
                            jnp.asarray([6, 6], jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
    jax.tree.map(lambda a, b2: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b2)), c_s, c_v)


def test_merge_cache_slots_selects_rows_by_mask():
    from repro.models import merge_cache_slots

    live = {"groups": ({"k": jnp.zeros((2, 3, 4, 5))},),   # (G, B, ...)
            "tail": ({"k": jnp.zeros((3, 4))},)}           # (B, ...)
    fresh = {"groups": ({"k": jnp.ones((2, 3, 4, 5))},),
             "tail": ({"k": jnp.ones((3, 4))},)}
    mask = jnp.asarray([True, False, True])
    merged = merge_cache_slots(live, fresh, mask)
    g = np.asarray(merged["groups"][0]["k"])
    t = np.asarray(merged["tail"][0]["k"])
    assert (g[:, 0] == 1).all() and (g[:, 2] == 1).all()
    assert (g[:, 1] == 0).all()
    assert (t[0] == 1).all() and (t[2] == 1).all() and (t[1] == 0).all()


@pytest.mark.parametrize("arch", ["chatglm3-6b", "mamba2-370m",
                                  "qwen3-moe-30b-a3b", "zamba2-1.2b"])
def test_jitted_init_matches_eager_init(arch):
    """init_params under jit (how the serving engine draws its weights)
    gives bit-identical values to the eager call: dense, SSM, MoE and
    hybrid blocks, including the scales that divide."""
    from repro.configs import get_config
    from repro.models import scaled_down
    cfg = scaled_down(get_config(arch))
    eager = init_params(jax.random.key(0), cfg)
    jitted = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
