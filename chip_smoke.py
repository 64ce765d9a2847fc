"""Bring-up check: the served path on a TPU at published widths.

  python chip_smoke.py             # one chip: chatglm3-6b, unfused + fused
  python chip_smoke.py --chips 4   # four chips: granite-3-8b, tensor-parallel

One chip runs three phases through the user's entry point,
``serve_workload`` with ``ServeConfig(fabric="wallclock", execute=True)``:
serve 8 requests unfused, check the fused Pallas decode kernel (it is in the
compiled decode step, and one call agrees with the unfused decode branch),
then serve the same requests fused.  ``--chips 4`` runs only the four-chip
phase: granite-3-8b (too large for one chip) served over a (1, 4) mesh, and
its prefill logits on one chip against four-way tensor parallelism, with
the depth cut to 4 layers.  Weights are random, drawn from ``--seed``.

Everything runs in this one process: a chip belongs to one process.  The
lines before the last report what ran; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
With no TPU backend, or when any phase fails, the script exits non-zero and
prints no ``ok`` line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: The one-chip serve phase's traffic: 8 requests, each answered in full.
SERVE_SPEC = dict(num_requests=8, prompt_lens=(64, 128), gen_lens=(32,),
                  seed=0)
#: The four-chip phase's traffic: a few requests through the sharded engine.
TP_SPEC = dict(num_requests=4, prompt_lens=(64,), gen_lens=(16,), seed=0)

#: Fused kernel vs the unfused decode branch, bf16 elementwise: one bf16
#: ULP at magnitude 1 is 2**-7 (7.8e-3), so a single rounding flip passes
#: and a wrong rotation, mask or scatter does not.
FUSED_RTOL = FUSED_ATOL = 1e-2
#: One chip vs four-way tensor parallel prefill logits (float32 logits of a
#: bf16 model whose partial sums are reduced in another order), relative to
#: the largest logit.
TP_LOGIT_TOL = 5e-2


class PhaseFailed(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _config(arch: str, reduced: bool):
    from repro.configs import get_config
    from repro.models import scaled_down
    cfg = get_config(arch)
    return scaled_down(cfg) if reduced else cfg


def _device_bytes(key: str) -> list[int]:
    import jax
    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def serve_phase(arch: str, *, reduced: bool, fused_decode: bool = False,
                mesh_shape=(1, 1), spec=None, max_batch: int = 4) -> dict:
    """Serve ``spec`` once; every request must be answered in full.

    The engine waits on its credit counter after every step and raises
    ``FaultDetected`` when a credit comes back short, so a run that returns
    had none.  Returns the counts, the seconds spent, each device's
    ``peak_bytes_in_use`` so far (where the engine's weights landed), and
    the generated tokens by request id.
    """
    from repro.serve import (RequestState, ServeConfig, WorkloadSpec,
                             serve_workload)
    cfg = _config(arch, reduced)
    spec = spec or WorkloadSpec(**SERVE_SPEC)
    config = ServeConfig(arch=arch, reduced=reduced, execute=True,
                         fabric="wallclock", max_batch=max_batch,
                         mesh_shape=tuple(mesh_shape),
                         fused_decode=fused_decode)
    t0 = time.perf_counter()
    out = serve_workload(spec, config=config)
    wall_s = time.perf_counter() - t0
    peak = _device_bytes("peak_bytes_in_use")
    reqs = out["requests"]
    done = [r for r in reqs if r.state is RequestState.DONE]
    tokens = {r.rid: np.asarray(r.generated) for r in done}
    _require(len(reqs) == spec.num_requests == len(done),
             f"{len(done)}/{spec.num_requests} requests answered")
    _require(out["metrics"].dropped == 0,
             f"{out['metrics'].dropped} requests dropped")
    for r in done:
        ids = tokens[r.rid]
        _require(ids.shape == (r.gen_len,),
                 f"request {r.rid}: {ids.shape[0]} of {r.gen_len} tokens")
        _require(bool(((ids >= 0) & (ids < cfg.vocab_size)).all()),
                 f"request {r.rid}: token id outside [0, {cfg.vocab_size})")
    setup_s = out["engine_setup_s"]
    return {"arch": cfg.name, "layers": cfg.num_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "fused_decode": fused_decode, "mesh": list(mesh_shape),
            "requests": spec.num_requests, "answered": len(done),
            "dropped": 0, "tokens": int(sum(t.size for t in tokens.values())),
            "setup_s": setup_s, "serve_s": wall_s - setup_s,
            "wall_s": wall_s, "peak_bytes_in_use": peak, "generated": tokens}


def fused_kernel_phase(arch: str, *, reduced: bool, max_len: int,
                       batch: int = 4, seed: int = 0,
                       require_kernel: bool = True) -> dict:
    """The fused decode kernel at ``arch``'s widths.

    Compiles the engine's fused decode step and looks for the Pallas kernel
    (``tpu_custom_call``) in it, then runs ``attention_block``'s decode
    branch fused and unfused on the same inputs: the v-cache must match
    exactly (a pure copy), the k-cache and the output within
    ``FUSED_RTOL``/``FUSED_ATOL``.
    """
    import functools

    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_decode_step
    from repro.models import init_cache
    from repro.models.layers import NO_SHARD, attention_block

    cfg = _config(arch, reduced)
    mesh = make_host_mesh(1, 1)
    with mesh:
        dec = make_decode_step(cfg, mesh, {
            "tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            "caches": jax.eval_shape(
                lambda: init_cache(cfg, batch, max_len=max_len)),
            "cache_len": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }, fused=True)
        t0 = time.perf_counter()
        hlo = jax.jit(dec.fn, in_shardings=dec.in_shardings,
                      out_shardings=dec.out_shardings,
                      donate_argnums=dec.donate_argnums).lower(
            *dec.abstract_args).compile().as_text()
        compile_s = time.perf_counter() - t0
    kernel_in_step = "tpu_custom_call" in hlo
    _require(kernel_in_step or not require_kernel,
             "compiled fused decode step holds no tpu_custom_call")

    dt = jnp.dtype(cfg.dtype)
    h, kh, hd, dm = (cfg.num_heads, cfg.num_kv_heads, cfg.qk_head_dim,
                     cfg.d_model)
    ks = jax.random.split(jax.random.key(seed), 7)
    p = {"wq": jax.random.normal(ks[0], (dm, h * hd)) / np.sqrt(dm),
         "wk": jax.random.normal(ks[1], (dm, kh * hd)) / np.sqrt(dm),
         "wv": jax.random.normal(ks[2], (dm, kh * hd)) / np.sqrt(dm),
         "wo": jax.random.normal(ks[3], (h * hd, dm)) / np.sqrt(h * hd)}
    p = {k: v.astype(dt) for k, v in p.items()}
    x = jax.random.normal(ks[4], (batch, 1, dm)).astype(dt)
    lens = jnp.asarray(np.linspace(0, max_len - 1, batch).astype(np.int32))
    cache = {"k": jax.random.normal(ks[5], (batch, max_len, kh, hd)).astype(dt),
             "v": jax.random.normal(ks[6], (batch, max_len, kh, hd)).astype(dt),
             "len": lens}

    def run(fused):
        fn = functools.partial(attention_block, cfg=cfg, ctx=NO_SHARD,
                               fused=fused)
        y, c = jax.jit(lambda x, p, pos, c: fn(x, p, positions=pos,
                                               cache=c))(
            x, p, lens[:, None], cache)
        return (np.asarray(y, np.float32), np.asarray(c["k"], np.float32),
                np.asarray(c["v"], np.float32))

    (y0, k0, v0), (y1, k1, v1) = run(False), run(True)
    _require(np.array_equal(v0, v1), "fused v-cache differs from unfused")
    diffs = {}
    for name, ref, got in (("out", y0, y1), ("k_cache", k0, k1)):
        _require(bool(np.isfinite(got).all()), f"fused {name} not finite")
        _require(np.allclose(got, ref, rtol=FUSED_RTOL, atol=FUSED_ATOL),
                 f"fused {name} differs from unfused by up to "
                 f"{np.abs(got - ref).max()}")
        diffs[name] = float(np.abs(got - ref).max())
    return {"arch": cfg.name, "heads": h, "kv_heads": kh, "head_dim": hd,
            "max_len": max_len, "kernel_in_step": kernel_in_step,
            "step_compile_s": compile_s, "max_abs_diff": diffs}


def tp_logits_phase(arch: str, *, reduced: bool, chips: int = 4,
                    depth: int = 4, batch: int = 2, prompt_len: int = 64,
                    seed: int = 0) -> dict:
    """Prefill logits on one chip vs tensor-parallel over ``chips`` chips.

    The config keeps its widths and is cut to ``depth`` layers.  The same
    weights (drawn once on one chip) and tokens go through ``forward`` on a
    one-device mesh and on a ``(1, chips)`` mesh with the repo's sharding
    rules; the logits of the real vocabulary (the padded tail is masked to
    -1e30) must agree within ``TP_LOGIT_TOL`` of the largest.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.models import forward, init_params
    from repro.runtime.sharding import (make_shard_ctx, param_specs,
                                        to_shardings)

    cfg = dataclasses.replace(_config(arch, reduced), num_layers=depth)
    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
               axis_types=(AxisType.Auto,) * 2)
    tp = make_host_mesh(1, chips)
    tokens = np.asarray(jax.random.randint(
        jax.random.key(seed + 1), (batch, prompt_len), 0, cfg.vocab_size,
        dtype=jnp.int32))

    def logits(mesh, params):
        shard = to_shardings(param_specs(params, cfg, mesh), mesh)
        ctx = make_shard_ctx(mesh)
        with mesh:
            fn = jax.jit(lambda p, t: forward(p, cfg, tokens=t, ctx=ctx),
                         in_shardings=(shard, NamedSharding(mesh, P())))
            out = fn(jax.device_put(params, shard), tokens)
            return np.asarray(out[..., :cfg.vocab_size])

    with one:
        p_abs = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
        params = jax.jit(
            lambda k: init_params(k, cfg),
            out_shardings=to_shardings(param_specs(p_abs, cfg, one), one))(
                jax.random.key(seed))
    ref = logits(one, params)
    got = logits(tp, params)
    del params
    gc.collect()
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    _require(bool(np.isfinite(got).all()), "tensor-parallel logits not finite")
    _require(err <= TP_LOGIT_TOL * scale,
             f"tensor-parallel logits differ by {err} (largest logit "
             f"{scale}, tolerance {TP_LOGIT_TOL} of it)")
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    return {"arch": cfg.name, "layers": depth, "chips": chips,
            "max_abs_diff": err, "max_abs_logit": scale,
            "argmax_agreement": agree}


def token_agreement(a: dict, b: dict) -> float:
    """Share of generated token positions on which two runs agree."""
    same = sum(int((a[r] == b[r]).sum()) for r in a)
    return same / max(sum(a[r].size for r in a), 1)


def _report(name: str, rec: dict) -> None:
    rec = {k: v for k, v in rec.items() if k != "generated"}
    print(f"[{name}] {json.dumps(rec, sort_keys=True)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: chatglm3-6b on one chip (default); 4: only the "
                         "four-chip tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; nothing "
              f"is measured off the chip", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2
    from repro.runtime.flags import enable_compile_cache
    cache_dir = enable_compile_cache()

    compiles = {"n": 0, "s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    print(f"jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}"
          f" ({devices[0].platform}), compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()

    if args.chips == 4:
        from repro.serve import WorkloadSpec
        rec = serve_phase("granite-3-8b", reduced=False, mesh_shape=(1, 4),
                          spec=WorkloadSpec(**TP_SPEC))
        _report("serve granite-3-8b tp4", rec)
        _report("tp logits granite-3-8b",
                tp_logits_phase("granite-3-8b", reduced=False,
                                seed=args.seed))
    else:
        unfused = serve_phase("chatglm3-6b", reduced=False)
        _report("serve chatglm3-6b unfused", unfused)
        max_len = max(SERVE_SPEC["prompt_lens"]) + max(SERVE_SPEC["gen_lens"])
        _report("fused kernel chatglm3-6b",
                fused_kernel_phase("chatglm3-6b", reduced=False,
                                   max_len=max_len, seed=args.seed))
        fused = serve_phase("chatglm3-6b", reduced=False, fused_decode=True)
        _report("serve chatglm3-6b fused", fused)
        print(f"fused vs unfused token agreement (information only): "
              f"{token_agreement(unfused['generated'], fused['generated'])}",
              flush=True)

    print(f"wall {time.perf_counter() - t0} s; {compiles['n']} compiles, "
          f"{compiles['s']} s compiling, {compiles['cache_hits']} "
          f"persistent-cache hits; peak_bytes_in_use per device "
          f"{_device_bytes('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
