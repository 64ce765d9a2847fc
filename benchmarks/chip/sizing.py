"""Compile-only sizing of each cell's programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python benchmarks/chip/sizing.py [cell ...] \
        [--batch N ...]

For each cell (all of ``BENCHMARK.json`` by default) this builds the
serving engine's two step programs at the cell's sizes, the decode step
and the slot prefill at the traffic's longest prompt with the cache sized
for its longest request, and compiles them for a v5e that is described,
not attached (one chip, or a 2x2 mesh for a four-chip cell).  It prints
``memory_analysis()`` of each: the bytes a chip would hold while the
program runs.  Nothing runs, so nothing here is a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import traffic  # noqa: E402
import work  # noqa: E402


def compile_cell(entry: dict, batch: int, topo) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.launch.steps import make_decode_step, make_slot_prefill_step
    from repro.models import init_cache

    cfg_file = work.load_config(entry["config"])
    mix = traffic.load_mix(entry["traffic"])
    cfg = get_config(cfg_file["arch"])
    shape = tuple(cfg_file["mesh_shape"])
    n_dev = shape[0] * shape[1]
    mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(shape),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    prompt, gen = traffic.longest(mix)
    max_len = prompt + gen
    out = []
    with mesh:
        caches = jax.eval_shape(lambda: init_cache(cfg, batch,
                                                   max_len=max_len))
        builds = {
            "decode_fn": make_decode_step(cfg, mesh, {
                "tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                "caches": caches,
                "cache_len": jax.ShapeDtypeStruct((batch,), jnp.int32)}),
            f"slot_prefill_step[{prompt}]": make_slot_prefill_step(
                cfg, mesh, {"tokens": jax.ShapeDtypeStruct(
                    (batch, prompt), jnp.int32)}, max_len=max_len),
        }
        for name, b in builds.items():
            fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                         out_shardings=b.out_shardings,
                         donate_argnums=b.donate_argnums)
            ma = fn.lower(*b.abstract_args).compile().memory_analysis()
            peak = getattr(ma, "peak_memory_in_bytes", None)
            out.append({
                "cell": entry["name"], "program": name, "batch": batch,
                "max_len": max_len, "devices": n_dev,
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "peak_bytes": peak,
            })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--batch", type=int, nargs="*",
                    help="batch sizes to try (default: the config's)")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["workloads"]:
        if args.cells and entry["name"] not in args.cells:
            continue
        batches = args.batch or [work.load_config(entry["config"])
                                 ["max_batch"]]
        for batch in batches:
            for rec in compile_cell(entry, batch, topo):
                print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
