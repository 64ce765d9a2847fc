"""A four-chip run with the exchange between chips left out.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmarks/chip/tests/exchange.py

Serves the four-chip cell at the program's scaled-down size over a (1, 4)
mesh of virtual CPU devices twice, past the harness's look for a chip:
once as it is, and once with the FFN's row-parallel product computed on
each device from its own shard with no all-reduce, so that each device
keeps its partial sum as if it were the whole.  Prints one JSON line:
both runs' ``correct`` and ``checks``.
"""

import json
import sys

import chip_paths  # noqa: F401  (before the modules below)
import run
import work
from smallrun import MIX, SMALL_LIMIT, dims

#: The four-chip configuration, and the cell it would serve.
CONFIG = "granite-3-8b-tp4"
CELL = f"{CONFIG}.chat-decode"


def leave_out_exchange():
    """Patch the program's FFN: each device multiplies its shard of the
    hidden activations by its rows of ``w_out`` and keeps the result."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch import steps
    from repro.models import model

    meshes = []
    make_ctx = steps.make_shard_ctx

    def make_shard_ctx(mesh):
        meshes.append(mesh)
        return make_ctx(mesh)

    def mlp_block(x, p, cfg, ctx):
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])
        h = ctx.constrain(h, ctx.dp, None, ctx.tp)
        local = jax.shard_map(
            lambda a, w: a @ w, mesh=meshes[-1],
            in_specs=(P(ctx.dp, None, ctx.tp), P(ctx.tp, None)),
            out_specs=P(ctx.dp, None, None), check_vma=False)
        return local(h, p["w_out"])

    steps.make_shard_ctx = make_shard_ctx
    model.mlp_block = mlp_block


def small_tp_run():
    """One run of the cell over its own mesh, 12 requests whose longest
    prompt and output fill 24 cache slots (the cache is split over the
    four devices by slot)."""
    bench = run.load_benchmark()
    cfg = work.load_config(CONFIG)
    cell = {"requests_per_s": 12.0,
            "check": {"rows": 12, "min_tokens": 1, "limit": SMALL_LIMIT},
            "trace": {"open_after_s": 15.0, "seconds": 4.0}}
    return run.run(CELL, seed=2**33 + 17, seconds=1.0, trace=False, cfg=cfg,
                   mix=MIX, cell=cell, bench=bench, reduced=True,
                   dims=dims(cfg), peak=work.load_peaks("TPU v5 lite"),
                   log=lambda s: None)


def main() -> int:
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    sound = small_tp_run()
    leave_out_exchange()
    broken = small_tp_run()
    print(json.dumps({name: {"correct": r["correct"], "checks": r["checks"]}
                      for name, r in (("sound", sound),
                                      ("broken", broken))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
