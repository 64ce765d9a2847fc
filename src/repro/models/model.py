"""Unified decoder-only LM covering all assigned architectures.

The layer stack is ``cfg.pattern`` repeated ``cfg.full_groups`` times (scanned
with stacked params — one HLO body regardless of depth) plus ``cfg.tail``
blocks applied outside the scan. Hybrid archs (Zamba2) reference a single
``shared`` transformer block from inside the pattern; its weights are stored
once and re-invoked per group, each invocation with its own KV cache.

Entry points:
  init_params(key, cfg)                  -> param pytree (eval_shape-able)
  forward(params, cfg, tokens|embeds)    -> logits           (train/prefill)
  init_cache(cfg, batch, max_len)        -> decode cache pytree
  decode_step(params, cfg, tokens, cache, cache_len) -> (logits, new_cache)
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (NO_SHARD, ShardCtx, attention_block, mamba_block,
                     mlp_block, moe_block, rms_norm)

Params = dict[str, Any]


def _normal(key, shape, div: float | None = None) -> jax.Array:
    """Standard-normal float32 draws (over ``div``), rounded as eager code.

    Under ``jit`` XLA would fuse the sampler's arithmetic with the scale
    that follows, and turn a division by a constant into a product with its
    reciprocal; both round differently.  Behind the barrier, jitted and
    eager :func:`init_params` draw bit-identical weights.
    """
    x = jax.random.normal(key, shape)
    if div is None:
        return jax.lax.optimization_barrier(x)
    x, div = jax.lax.optimization_barrier((x, jnp.float32(div)))
    return x / div


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #
def _init_attn(key, cfg: ModelConfig, dt):
    hd = cfg.qk_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(cfg.num_heads * hd)
    return {
        "wq": (_normal(ks[0], (d, cfg.num_heads * hd)) * s).astype(dt),
        "wk": (_normal(ks[1], (d, cfg.num_kv_heads * hd)) * s).astype(dt),
        "wv": (_normal(ks[2], (d, cfg.num_kv_heads * hd)) * s).astype(dt),
        "wo": (_normal(ks[3], (cfg.num_heads * hd, d)) * so).astype(dt),
    }


def _init_mlp(key, cfg: ModelConfig, dt):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "w_in": (_normal(ks[0], (d, f)) * s_in).astype(dt),
        "w_out": (_normal(ks[1], (f, d)) * s_out).astype(dt),
    }
    if cfg.gated_mlp:
        p["w_gate"] = (_normal(ks[2], (d, f)) * s_in).astype(dt)
    return p


def _init_moe(key, cfg: ModelConfig, dt):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "w_router": (_normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (_normal(ks[1], (e, d, f)) * s_in).astype(dt),
        "w_in": (_normal(ks[2], (e, d, f)) * s_in).astype(dt),
        "w_out": (_normal(ks[3], (e, f, d)) * s_out).astype(dt),
    }


def _init_mamba(key, cfg: ModelConfig, dt):
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    h = cfg.ssm_num_heads
    conv_ch = di + 2 * n
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(d)
    return {
        "w_z": (_normal(ks[3], (d, di)) * s).astype(dt),
        "w_x": (_normal(ks[4], (d, di)) * s).astype(dt),
        "w_bc": (_normal(ks[5], (d, 2 * n)) * s).astype(dt),
        "w_dt": (_normal(ks[6], (d, h)) * s).astype(dt),
        "w_conv": _normal(ks[1], (cfg.conv_width, conv_ch),
                          div=math.sqrt(cfg.conv_width)).astype(dt),
        "a_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),  # softplus ~= 0.12
        "d_skip": jnp.ones((h,), jnp.float32),
        "w_norm": jnp.zeros((di,), jnp.float32),
        "w_out": _normal(ks[2], (di, d), div=math.sqrt(di)).astype(dt),
    }


def _init_block(key, kind: str, cfg: ModelConfig, dt):
    d = cfg.d_model
    if kind == "mamba":
        return {"norm1": jnp.zeros((d,), jnp.float32),
                "mamba": _init_mamba(key, cfg, dt)}
    if kind == "shared_attn":
        return {}  # weights live once at top level (params["shared"])
    k1, k2 = jax.random.split(key)
    p = {"norm1": jnp.zeros((d,), jnp.float32),
         "norm2": jnp.zeros((d,), jnp.float32),
         "attn": _init_attn(k1, cfg, dt)}
    if kind == "attn_moe":
        p["moe"] = _init_moe(k2, cfg, dt)
    else:
        p["mlp"] = _init_mlp(k2, cfg, dt)
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    d, v = cfg.d_model, cfg.vocab_size
    n_pos = len(cfg.pattern)
    keys = jax.random.split(key, n_pos + len(cfg.tail) + 4)

    def stack_init(k, kind):
        def one(kk):
            return _init_block(kk, kind, cfg, dt)
        # One layer at a time: the float32 draws never exceed one layer.
        return jax.lax.map(one, jax.random.split(k, cfg.full_groups))

    groups = tuple(
        stack_init(keys[i], kind) for i, kind in enumerate(cfg.pattern))
    tail = tuple(
        _init_block(keys[n_pos + i], kind, cfg, dt)
        for i, kind in enumerate(cfg.tail))
    vp = cfg.vocab_padded
    params: Params = {
        "embed": (_normal(keys[-1], (vp, d)) * 0.02).astype(dt),
        "groups": groups,
        "tail": tail,
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if cfg.uses_shared_block:
        params["shared"] = _init_block(keys[-2], "attn", cfg, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(keys[-3], (d, vp),
                                    div=math.sqrt(d)).astype(dt)
    return params


# --------------------------------------------------------------------------- #
# Blocks
# --------------------------------------------------------------------------- #
def _apply_block(h, bp, kind, cfg: ModelConfig, ctx: ShardCtx, *,
                 positions, cache=None, shared=None, fused=False):
    """One decoder block; returns (h, new_cache)."""
    if kind == "shared_attn":
        bp = shared
        kind = "attn"
    window = cfg.sliding_window if kind == "local" else 0
    if kind == "mamba":
        m_in = rms_norm(h, bp["norm1"], cfg.norm_eps)
        m_out, new_cache = mamba_block(m_in, bp["mamba"], cfg, ctx,
                                       cache=cache)
        return h + m_out, new_cache
    a_in = rms_norm(h, bp["norm1"], cfg.norm_eps)
    a_out, new_cache = attention_block(a_in, bp["attn"], cfg, ctx,
                                       positions=positions, window=window,
                                       cache=cache, fused=fused)
    h = h + a_out
    f_in = rms_norm(h, bp["norm2"], cfg.norm_eps)
    if "moe" in bp:
        f_out = moe_block(f_in, bp["moe"], cfg, ctx)
    else:
        f_out = mlp_block(f_in, bp["mlp"], cfg, ctx)
    return h + f_out, new_cache


def _run_stack(params, h, cfg: ModelConfig, ctx: ShardCtx, *,
               positions, caches=None, cache_len=None, remat=False,
               unroll_groups=False, fused=False):
    """Scan over full groups, then the tail. Returns (h, new_caches).

    ``remat`` checkpoints each group (recompute in backward — required to fit
    4k-seq training activations). ``unroll_groups`` replaces the scan with a
    python loop (used by the dry-run's cost-accounting variants, since XLA's
    cost_analysis counts a while body once regardless of trip count).
    """
    shared = params.get("shared")
    use_cache = caches is not None

    def with_len(c):
        if c is None or not use_cache:
            return None
        c = dict(c)
        c["len"] = cache_len
        return c

    def group_body(carry, xs):
        hh = carry
        gparams, gcache = xs
        new_entries = []
        for i, kind in enumerate(cfg.pattern):
            entry = gcache[i] if use_cache else None
            hh, new_c = _apply_block(
                hh, gparams[i], kind, cfg, ctx, positions=positions,
                cache=with_len(entry), shared=shared, fused=fused)
            if use_cache:
                new_c = {k: v for k, v in (new_c or {}).items() if k != "len"}
            new_entries.append(new_c if use_cache else None)
        return hh, tuple(new_entries) if use_cache else None

    if remat:
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (params["groups"],
          caches["groups"] if use_cache else
          tuple(None for _ in cfg.pattern))
    if unroll_groups:
        new_list = []
        for g in range(cfg.full_groups):
            take = jax.tree.map(lambda x: x[g], params["groups"])
            cache_g = (jax.tree.map(lambda x: x[g], caches["groups"])
                       if use_cache else xs[1])
            h, new_g = group_body(h, (take, cache_g))
            new_list.append(new_g)
        new_group_caches = (jax.tree.map(lambda *z: jnp.stack(z), *new_list)
                            if use_cache else None)
    elif use_cache:
        h, new_group_caches = jax.lax.scan(group_body, h, xs)
    else:
        # No caches: xs has a None component; build a scan over params only.
        def body(carry, gparams):
            hh, _ = group_body(carry, (gparams, xs[1]))
            return hh, None
        h, _ = jax.lax.scan(body, h, params["groups"])
        new_group_caches = None

    new_tail = []
    for i, kind in enumerate(cfg.tail):
        entry = caches["tail"][i] if use_cache else None
        h, new_c = _apply_block(h, params["tail"][i], kind, cfg, ctx,
                                positions=positions, cache=with_len(entry),
                                shared=shared, fused=fused)
        if use_cache:
            new_c = {k: v for k, v in (new_c or {}).items() if k != "len"}
        new_tail.append(new_c)
    new_caches = None
    if use_cache:
        new_caches = {"groups": new_group_caches, "tail": tuple(new_tail)}
    return h, new_caches


# --------------------------------------------------------------------------- #
# Forward (train / prefill)
# --------------------------------------------------------------------------- #
def embed_tokens(params, tokens, cfg: ModelConfig, ctx: ShardCtx):
    h = jnp.take(params["embed"], tokens, axis=0)
    return ctx.constrain(h, ctx.dp, None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_dtype_barrier(x, dtype_str: str):
    """Identity; casts the cotangent back to the primal dtype.

    The f32 loss seeds an f32 cotangent chain (dtype promotion keeps it f32
    through every einsum VJP), which doubles the wire size of every
    tensor-parallel activation all-reduce in the backward pass. Placing this
    barrier at the logits boundary makes the whole decoder backward run in
    the activation dtype (bf16 at scale) — §Perf iteration 8.
    """
    return x


def _gdb_fwd(x, dtype_str):
    return x, None


def _gdb_bwd(dtype_str, _, g):
    return (g.astype(jnp.dtype(dtype_str)),)


_grad_dtype_barrier.defvjp(_gdb_fwd, _gdb_bwd)


def logits_from_hidden(params, h, cfg: ModelConfig, ctx: ShardCtx):
    h = _grad_dtype_barrier(h, cfg.dtype)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", h, head,
                        preferred_element_type=jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        # Mask padded vocabulary columns (keeps the model-axis sharding).
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    return ctx.constrain(logits, ctx.dp, None, ctx.tp)


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, ctx: ShardCtx = NO_SHARD, remat=False,
            unroll_groups=False):
    """Full-sequence forward -> logits (B, S, V)."""
    if (tokens is None) == (embeds is None):
        raise ValueError("provide exactly one of tokens/embeds")
    h = embed_tokens(params, tokens, cfg, ctx) if embeds is None else \
        ctx.constrain(embeds.astype(jnp.dtype(cfg.dtype)), ctx.dp, None, None)
    b, s = h.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, _ = _run_stack(params, h, cfg, ctx, positions=positions, remat=remat,
                      unroll_groups=unroll_groups)
    return logits_from_hidden(params, h, cfg, ctx)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE; logits (B,S,V) f32, targets (B,S) int32.

    Written so a vocab-sharded logits tensor never gets gathered: the gold
    logit is a one-hot einsum (fuses into a local reduction + psum over the
    vocab shards) and the logsumexp is an explicit max/sum pair (local
    reductions + scalar-per-token collectives). With take_along_axis /
    jax.scipy logsumexp, the SPMD partitioner materialized the full f32
    logits on every device (38 GB/step at qwen3-30b train — §Perf iter. 4).
    """
    from repro.runtime.flags import baseline_mode
    logits = logits[:, :-1]
    targets = targets[:, 1:]
    if baseline_mode():  # paper-faithful baseline: naive CE formulation
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
    else:
        lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(logits - lmax), axis=-1)) + lmax[..., 0]
        onehot = jax.nn.one_hot(targets, logits.shape[-1],
                                dtype=logits.dtype)
        gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = lse - gold
    if mask is not None:
        mask = mask[:, 1:].astype(nll.dtype)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def prefill(params, cfg: ModelConfig, *, caches, tokens=None, embeds=None,
            ctx: ShardCtx = NO_SHARD):
    """Batched prefill: full-sequence forward that also populates caches.

    Returns (logits (B,S,V), caches with cache_len advanced by S).
    """
    if (tokens is None) == (embeds is None):
        raise ValueError("provide exactly one of tokens/embeds")
    h = embed_tokens(params, tokens, cfg, ctx) if embeds is None else \
        ctx.constrain(embeds.astype(jnp.dtype(cfg.dtype)), ctx.dp, None, None)
    b, s = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, new_caches = _run_stack(params, h, cfg, ctx, positions=positions,
                               caches=caches, cache_len=jnp.int32(0))
    return logits_from_hidden(params, h, cfg, ctx), new_caches


# --------------------------------------------------------------------------- #
# Decode (single-token serve step with caches)
# --------------------------------------------------------------------------- #
def _cache_entry(kind: str, cfg: ModelConfig, batch: int, max_len: int, dt):
    if kind == "mamba":
        return {
            # SSM state accumulates over the whole sequence -> keep f32.
            "ssm": jnp.zeros((batch, cfg.ssm_num_heads,
                              cfg.d_inner // cfg.ssm_num_heads,
                              cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1,
                               cfg.d_inner + 2 * cfg.ssm_state), dt),
        }
    length = max_len
    if kind == "local" and cfg.sliding_window:
        length = min(max_len, cfg.sliding_window)  # ring buffer
    kv_dt = jnp.int8 if cfg.kv_quant else dt
    entry = {
        "k": jnp.zeros((batch, length, cfg.num_kv_heads, cfg.qk_head_dim),
                       kv_dt),
        "v": jnp.zeros((batch, length, cfg.num_kv_heads, cfg.qk_head_dim),
                       kv_dt),
    }
    if cfg.kv_quant:
        entry["k_scale"] = jnp.zeros((batch, length, cfg.num_kv_heads, 1),
                                     jnp.float32)
        entry["v_scale"] = jnp.zeros((batch, length, cfg.num_kv_heads, 1),
                                     jnp.float32)
    return entry


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str | None = None):
    dt = jnp.dtype(dtype or cfg.dtype)

    def stacked(kind):
        one = _cache_entry("attn" if kind == "shared_attn" else kind,
                           cfg, batch, max_len, dt)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.full_groups, *x.shape)),
            one)

    return {
        "groups": tuple(stacked(kind) for kind in cfg.pattern),
        "tail": tuple(
            _cache_entry("attn" if k == "shared_attn" else k,
                         cfg, batch, max_len, dt) for k in cfg.tail),
    }


def decode_step(params, cfg: ModelConfig, tokens, caches, cache_len,
                *, ctx: ShardCtx = NO_SHARD, fused: bool = False):
    """One decode step: tokens (B, 1) int32 -> (logits (B,1,V), new caches).

    ``fused=True`` routes every attention block through the fused Pallas
    decode kernel (``repro.kernels.decode_attention``) — one launch per
    layer instead of the separate rope/scatter/attend ops, bit-identical
    tokens (DESIGN.md §12).

    ``cache_len`` is the number of tokens already in the cache; the new
    token is written at that index (ring-buffered for local layers).  It is
    either a scalar (every slot at the same position — the wave-boundary
    path) or a per-slot (B,) vector: each batch row attends over its own
    valid prefix and takes its own rotary position, which is what lets the
    serving loop hold requests at different sequence offsets in one batch
    and admit new requests mid-wave (DESIGN.md §6).
    """
    h = embed_tokens(params, tokens, cfg, ctx)
    b = tokens.shape[0]
    lens = jnp.asarray(cache_len, jnp.int32)
    if lens.ndim == 0:
        lens = jnp.broadcast_to(lens, (b,))
    positions = lens[:, None]                       # (B, 1) per-slot position
    h, new_caches = _run_stack(params, h, cfg, ctx, positions=positions,
                               caches=caches, cache_len=lens, fused=fused)
    return logits_from_hidden(params, h, cfg, ctx), new_caches


def merge_cache_slots(live, fresh, slot_mask):
    """Replace the cache rows selected by ``slot_mask`` with ``fresh`` rows.

    The prefill-into-slot path (DESIGN.md §6) runs a full-batch prefill of
    the newly admitted prompts — batch rows are independent, so the rows of
    still-running requests in ``fresh`` are garbage — and this merge keeps
    ``live`` rows wherever ``slot_mask`` is False.  Group caches are stacked
    ``(full_groups, B, ...)`` (batch axis 1), tail caches are ``(B, ...)``
    (batch axis 0); see ``init_cache``.
    """
    mask = jnp.asarray(slot_mask, bool)

    def merge_group(live_leaf, f):
        m = mask.reshape((1, mask.shape[0]) + (1,) * (live_leaf.ndim - 2))
        return jnp.where(m, f.astype(live_leaf.dtype), live_leaf)

    def merge_tail(live_leaf, f):
        m = mask.reshape((mask.shape[0],) + (1,) * (live_leaf.ndim - 1))
        return jnp.where(m, f.astype(live_leaf.dtype), live_leaf)

    return {"groups": jax.tree.map(merge_group, live["groups"],
                                   fresh["groups"]),
            "tail": jax.tree.map(merge_tail, live["tail"], fresh["tail"])}
