"""Step builders: train_step / prefill_step / decode_step with full sharding.

Every step is built against a mesh and returns (fn, in_shardings,
out_shardings, donate) ready for ``jax.jit`` — used identically by the real
launchers (train.py/serve.py) and the dry-run (ShapeDtypeStructs).

The paper's mechanisms are wired in here:
  * the step's inputs are placed by the *multicast* dispatcher (one host
    call; see repro.core.dispatch),
  * every step emits a *credit counter* scalar (repro.core.sync): each device
    contributes one credit gated on its outputs being finite; the host blocks
    on that single scalar — O(1) completion sync + poisoned-shard detection.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sync import emit_credits
from repro.models import ModelConfig, cross_entropy, decode_step as model_decode
from repro.models import (forward, init_cache, init_params, merge_cache_slots,
                          prefill as model_prefill)
from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         init_opt_state)
from repro.runtime.sharding import (batch_specs, cache_specs, make_shard_ctx,
                                    opt_specs, param_specs, to_shardings)


@dataclasses.dataclass
class StepBundle:
    fn: Any
    in_shardings: Any
    out_shardings: Any
    donate_argnums: tuple[int, ...]
    abstract_args: tuple        # ShapeDtypeStruct pytrees, jit-ready
    meta: dict


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


def _loss_fn(params, batch, cfg, ctx, *, remat, unroll_groups=False):
    if "embeds" in batch:
        logits = forward(params, cfg, embeds=batch["embeds"], ctx=ctx,
                         remat=remat, unroll_groups=unroll_groups)
        labels = batch["labels"]
    else:
        logits = forward(params, cfg, tokens=batch["tokens"], ctx=ctx,
                         remat=remat, unroll_groups=unroll_groups)
        labels = batch["tokens"]
    return cross_entropy(logits, labels)


def make_train_step(cfg: ModelConfig, mesh, batch_abstract,
                    opt_cfg: AdamWConfig | None = None, *, remat: bool = True,
                    unroll_groups: bool = False) -> StepBundle:
    opt_cfg = opt_cfg or AdamWConfig()
    ctx = make_shard_ctx(mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            functools.partial(_loss_fn, cfg=cfg, ctx=ctx, remat=remat,
                              unroll_groups=unroll_groups))(params, batch)
        # Pin gradients to the parameter sharding: the data-axis gradient
        # reduction lowers as reduce-scatter (each device keeps only its
        # FSDP shard) instead of a full all-reduce — 2x less wire traffic
        # (EXPERIMENTS.md §Perf iteration 3).
        grads = jax.lax.with_sharding_constraint(
            grads, to_shardings(param_specs(_abstract_params(cfg), cfg, mesh),
                                mesh))
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        new_params, new_state = adamw_update(params, grads, opt_state,
                                             opt_cfg)
        metrics = {"loss": loss, "grad_norm": gnorm}
        credits = emit_credits({"loss": loss, "p": new_params}, mesh)
        metrics["credits"] = credits
        return new_params, new_state, metrics

    p_abs = _abstract_params(cfg)
    o_abs = jax.eval_shape(init_opt_state, p_abs)
    p_spec = param_specs(p_abs, cfg, mesh)
    o_spec = opt_specs(p_spec)
    b_spec = batch_specs(batch_abstract, mesh)
    m_spec = {"loss": P(), "grad_norm": P(), "credits": P()}
    return StepBundle(
        fn=train_step,
        in_shardings=to_shardings((p_spec, o_spec, b_spec), mesh),
        out_shardings=to_shardings((p_spec, o_spec, m_spec), mesh),
        donate_argnums=(0, 1),
        abstract_args=(p_abs, o_abs, batch_abstract),
        meta={"kind": "train", "param_spec": p_spec, "batch_spec": b_spec},
    )


def make_prefill_step(cfg: ModelConfig, mesh, batch_abstract, *,
                      max_len: int, unroll_groups: bool = False) -> StepBundle:
    ctx = make_shard_ctx(mesh)
    some = next(iter(batch_abstract.values()))
    batch_size = some.shape[0]

    def prefill_step(params, batch):
        caches = init_cache(cfg, batch_size, max_len=max_len)
        kw = ({"embeds": batch["embeds"]} if "embeds" in batch
              else {"tokens": batch["tokens"]})
        logits, caches = model_prefill(params, cfg, caches=caches, ctx=ctx,
                                       **kw)
        last = logits[:, -1]
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        credits = emit_credits({"last": last}, mesh)
        return {"next_token": next_tok, "caches": caches,
                "credits": credits}

    p_abs = _abstract_params(cfg)
    p_spec = param_specs(p_abs, cfg, mesh)
    b_spec = batch_specs(batch_abstract, mesh)
    c_abs = jax.eval_shape(lambda: init_cache(cfg, batch_size,
                                              max_len=max_len))
    c_spec = cache_specs(c_abs, cfg, mesh)
    from repro.runtime.sharding import data_spec_for
    out_spec = {"next_token": P(data_spec_for(batch_size, mesh)),
                "caches": c_spec, "credits": P()}
    return StepBundle(
        fn=prefill_step,
        in_shardings=to_shardings((p_spec, b_spec), mesh),
        out_shardings=to_shardings(out_spec, mesh),
        donate_argnums=(),
        abstract_args=(p_abs, batch_abstract),
        meta={"kind": "prefill", "param_spec": p_spec},
    )


def make_slot_prefill_step(cfg: ModelConfig, mesh, batch_abstract, *,
                           max_len: int) -> StepBundle:
    """Prefill newly admitted prompts *into freed slots* of live caches.

    The mid-wave admission path (DESIGN.md §6): ``fn(params, batch,
    live_caches, slot_mask)`` runs a full-batch prefill of the new prompts —
    batch rows are independent, so rows of still-running requests compute
    garbage that is discarded — and merges only the ``slot_mask`` rows into
    the donated live caches.  Rows of running requests keep their KV state
    bit-for-bit, which is what makes continuous batching produce the same
    tokens as the wave-boundary path.
    """
    ctx = make_shard_ctx(mesh)
    some = next(iter(batch_abstract.values()))
    batch_size = some.shape[0]

    def slot_prefill_step(params, batch, live_caches, slot_mask):
        fresh = init_cache(cfg, batch_size, max_len=max_len)
        kw = ({"embeds": batch["embeds"]} if "embeds" in batch
              else {"tokens": batch["tokens"]})
        logits, fresh = model_prefill(params, cfg, caches=fresh, ctx=ctx,
                                      **kw)
        last = logits[:, -1]
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        merged = merge_cache_slots(live_caches, fresh, slot_mask)
        credits = emit_credits({"last": last}, mesh)
        return {"next_token": next_tok, "caches": merged, "credits": credits}

    p_abs = _abstract_params(cfg)
    p_spec = param_specs(p_abs, cfg, mesh)
    b_spec = batch_specs(batch_abstract, mesh)
    c_abs = jax.eval_shape(lambda: init_cache(cfg, batch_size,
                                              max_len=max_len))
    c_spec = cache_specs(c_abs, cfg, mesh)
    from repro.runtime.sharding import data_spec_for
    out_spec = {"next_token": P(data_spec_for(batch_size, mesh)),
                "caches": c_spec, "credits": P()}
    mask_abs = jax.ShapeDtypeStruct((batch_size,), jnp.bool_)
    return StepBundle(
        fn=slot_prefill_step,
        in_shardings=to_shardings((p_spec, b_spec, c_spec, P()), mesh),
        out_shardings=to_shardings(out_spec, mesh),
        donate_argnums=(2,),   # live caches updated in place
        abstract_args=(p_abs, batch_abstract, c_abs, mask_abs),
        meta={"kind": "slot_prefill", "param_spec": p_spec},
    )


def make_decode_step(cfg: ModelConfig, mesh, specs, *,
                     unroll_groups: bool = False,
                     fused: bool = False) -> StepBundle:
    """specs: {"tokens": (B,1)|(B,), "caches": pytree,
    "cache_len": scalar|(B,)}.

    A per-slot ``cache_len`` vector lets each batch row decode at its own
    sequence offset (continuous batching, DESIGN.md §6); a scalar keeps the
    legacy batch-wide position (every row at the same offset).  ``(B,)``
    tokens have the shape and sharding of the step's own ``next_token``, so
    a step can take its predecessor's output where it lies on the devices.

    ``fused=True`` builds the step on the fused Pallas decode-attention
    kernel (one launch per layer, bit-identical tokens — DESIGN.md §12).
    """
    ctx = make_shard_ctx(mesh)

    def decode_fn(params, tokens, caches, cache_len):
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        logits, new_caches = model_decode(params, cfg, tokens, caches,
                                          cache_len, ctx=ctx, fused=fused)
        next_tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        credits = emit_credits({"logits": logits}, mesh)
        return {"next_token": next_tok, "caches": new_caches,
                "credits": credits}

    p_abs = _abstract_params(cfg)
    p_spec = param_specs(p_abs, cfg, mesh)
    c_spec = cache_specs(specs["caches"], cfg, mesh)
    t_spec = batch_specs(specs["tokens"], mesh)
    from repro.runtime.sharding import data_spec_for
    batch_size = specs["tokens"].shape[0]
    out_spec = {"next_token": P(data_spec_for(batch_size, mesh)),
                "caches": c_spec, "credits": P()}
    return StepBundle(
        fn=decode_fn,
        in_shardings=to_shardings((p_spec, t_spec, c_spec, P()), mesh),
        out_shardings=to_shardings(out_spec, mesh),
        donate_argnums=(2,),   # cache updated in place
        abstract_args=(p_abs, specs["tokens"], specs["caches"],
                       specs["cache_len"]),
        meta={"kind": "decode", "param_spec": p_spec, "fused": fused},
    )


def bundle_for(cfg: ModelConfig, mesh, shape_name: str, specs: dict, *,
               unroll_groups: bool = False) -> StepBundle:
    """Route an (arch x shape) cell to the right step builder."""
    from repro.configs.shapes import SHAPES
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        return make_train_step(cfg, mesh, specs,
                               unroll_groups=unroll_groups)
    if kind == "prefill":
        return make_prefill_step(cfg, mesh, specs,
                                 max_len=SHAPES[shape_name]["seq"],
                                 unroll_groups=unroll_groups)
    return make_decode_step(cfg, mesh, specs, unroll_groups=unroll_groups)
