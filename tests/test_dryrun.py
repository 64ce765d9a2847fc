"""Dry-run machinery tests: HLO collective parsing (trip counts, operand
byte rules) and an end-to-end miniature dry-run on 8 virtual devices."""

import textwrap

import pytest

from repro.launch.dryrun import _shape_bytes, parse_collectives


def test_shape_bytes():
    assert _shape_bytes("f32[4,8]") == 128
    assert _shape_bytes("bf16[16]") == 32
    assert _shape_bytes("f32[]") == 4
    assert _shape_bytes("s32[2,2,2]") == 32


FIXTURE = textwrap.dedent("""
    HloModule test

    %add (a: f32[], b: f32[]) -> f32[] {
      ROOT %r = f32[] add(f32[] %a, f32[] %b)
    }

    %cond (s: (s32[], f32[64])) -> pred[] {
      %c = s32[] constant(7)
      %i = s32[] get-tuple-element((s32[], f32[64]) %s), index=0
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    %body (s: (s32[], f32[64])) -> (s32[], f32[64]) {
      %x = f32[64]{0} get-tuple-element(%s), index=1
      %ar = f32[64]{0} all-reduce(%x), channel_id=1, replica_groups=[4,2]<=[8], to_apply=%add
      ROOT %t = (s32[], f32[64]) tuple(%i2, %ar)
    }

    ENTRY %main (p: f32[64]) -> f32[64] {
      %ag = f32[64]{0} all-gather(f32[8]{0} %p), channel_id=2, replica_groups=[1,8]<=[8], dimensions={0}
      %w = (s32[], f32[64]) while(%init), condition=%cond, body=%body
      %rs = f32[8]{0} reduce-scatter(f32[64]{0} %q), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
      ROOT %out = f32[64]{0} get-tuple-element(%w), index=1
    }
""")


def test_parse_collectives_trip_counts_and_bytes():
    out = parse_collectives(FIXTURE)
    by_kind = {o["kind"]: o for o in out["ops"]}
    # all-reduce inside the while body: multiplied by the trip count (7).
    ar = by_kind["all-reduce"]
    assert ar["multiplier"] == 7
    assert ar["operand_bytes"] == 64 * 4
    assert ar["group_size"] == 2
    # all-gather at top level: operand = result / group.
    ag = by_kind["all-gather"]
    assert ag["multiplier"] == 1
    assert ag["operand_bytes"] == 64 * 4 // 8
    # reduce-scatter: operand = result * group.
    rs = by_kind["reduce-scatter"]
    assert rs["operand_bytes"] == 8 * 4 * 8
    # totals multiply by trips.
    assert out["per_device_bytes_by_kind"]["all-reduce"] == 7 * 256
    # ring-effective: AR = 2x operand x (g-1)/g.
    assert ar["effective_bytes"] == int(2 * 256 * 1 / 2)


@pytest.mark.slow
def test_miniature_dryrun_cell_end_to_end():
    """Run the real dry-run path (steps + shardings + compile + analysis)
    on a 4x2 mesh with a reduced config, in a subprocess."""
    from conftest import run_py
    r = run_py("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.shapes import config_for_shape
from repro.launch.mesh import make_mesh
from repro.launch.steps import bundle_for
from repro.launch.dryrun import parse_collectives
from repro.models import scaled_down
import dataclasses

mesh = make_mesh((4, 2), ("data", "model"))
cfg = scaled_down(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, num_heads=4, num_kv_heads=2, moe_groups=8)
specs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
bundle = bundle_for(cfg, mesh, "train_4k", specs)
with mesh:
    compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings,
                       donate_argnums=bundle.donate_argnums
                       ).lower(*bundle.abstract_args).compile()
ma = compiled.memory_analysis()
assert ma.peak_memory_in_bytes > 0
colls = parse_collectives(compiled.as_text())
kinds = set(colls["per_device_bytes_by_kind"])
assert colls["per_device_bytes_total"] > 0
print("OK", sorted(k for k, v in colls["per_device_bytes_by_kind"].items()
                   if v > 0))
""", devices=8)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_decode_bundle_compiles_with_kv_quant():
    from conftest import run_py
    r = run_py("""
import jax, jax.numpy as jnp, dataclasses
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_decode_step
from repro.models import init_cache, scaled_down

mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(scaled_down(get_config("granite-3-8b")),
                          kv_quant=True, num_heads=4, num_kv_heads=2)
caches = jax.eval_shape(lambda: init_cache(cfg, 4, max_len=64))
specs = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32),
         "caches": caches,
         "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}
bundle = make_decode_step(cfg, mesh, specs)
with mesh:
    compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings,
                       donate_argnums=bundle.donate_argnums
                       ).lower(*bundle.abstract_args).compile()
print("OK", compiled.memory_analysis().peak_memory_in_bytes > 0)
""", devices=8)
    assert r.returncode == 0 and "OK True" in r.stdout, r.stderr[-3000:]
