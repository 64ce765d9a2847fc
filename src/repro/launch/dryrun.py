import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, compiles
and fits, and extract the roofline inputs from the compiled artifact.

For each cell:
  * build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * build the step (train/prefill/decode) with full shardings,
  * ``jax.jit(...).lower(**ShapeDtypeStructs).compile()`` — no allocation,
  * record memory_analysis (per-device bytes: proves it fits),
  * record cost_analysis (flops/bytes as reported; see scan caveat),
  * parse the partitioned HLO for collectives: op kind, operand bytes,
    replica-group size, and the enclosing while-loop trip-count multiplier
    (scan bodies execute trip-count times but appear once in HLO).

Usage:
  python -m repro.launch.dryrun --arch starcoder2-15b --shape train_4k \
      --mesh single --out results/
  python -m repro.launch.dryrun --all --mesh both --out results/
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import (SHAPE_NAMES, config_for_shape, input_specs,
                                  shape_applicable)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import bundle_for

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis(), or {} when the backend reports none."""
    return compiled.cost_analysis() or {}


def _shape_bytes(type_str: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", type_str)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def parse_collectives(hlo: str) -> dict:
    """Per-computation collective census with while-loop trip multipliers."""
    # 1) split into computations. NOTE: computation headers may have tuple
    # parameters with nested parens — the greedy `\(.*\)` handles them.
    comp_re = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{$")
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = comp_re.match(line.strip())
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)

    # 2) while nesting: comp -> (body_comp, cond_comp)
    while_re = re.compile(r"while\(.*\).*condition=%?([\w\.\-]+),"
                          r"\s*body=%?([\w\.\-]+)")
    parent: dict[str, tuple[str, str]] = {}  # body -> (parent_comp, cond)
    for cname, lines in comps.items():
        for ln in lines:
            m = while_re.search(ln)
            if m:
                parent[m.group(2)] = (cname, m.group(1))

    # 3) trip counts from cond computations (largest s32 constant)
    def trip_count(cond: str) -> int | None:
        best = None
        for ln in comps.get(cond, []):
            for m in re.finditer(r"constant\((\d+)\)", ln):
                v = int(m.group(1))
                best = v if best is None else max(best, v)
        return best

    def multiplier(comp: str) -> int:
        mult = 1
        seen = set()
        c = comp
        while c in parent and c not in seen:
            seen.add(c)
            pcomp, cond = parent[c]
            t = trip_count(cond)
            mult *= t if t else 1
            c = pcomp
        return mult

    # 4) collectives per computation
    coll_re = re.compile(
        r"=\s*([a-z0-9]+\[[\d,]*\])[^=]*\b(" + "|".join(COLLECTIVES)
        + r")\(([^)]*)\)(.*)$")
    group_re = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
    ops = []
    totals = {k: 0.0 for k in COLLECTIVES}
    for cname, lines in comps.items():
        mult = multiplier(cname)
        for ln in lines:
            m = coll_re.search(ln)
            if not m:
                continue
            result_t, kind, operands, tail_txt = m.groups()
            result_b = _shape_bytes(result_t)
            gm = group_re.search(ln)
            gsize = int(gm.group(2)) if gm else 1
            if kind == "all-gather":
                operand_b = result_b // max(gsize, 1)
            elif kind == "reduce-scatter":
                operand_b = result_b * gsize
            else:
                operand_b = result_b
            # Ring-model wire bytes per device: all-reduce moves ~2x its
            # operand (reduce-scatter phase + all-gather phase); the others
            # move ~(g-1)/g x their payload (~1x).
            if kind == "all-reduce":
                eff = 2 * operand_b
            elif kind == "all-gather":
                eff = result_b  # (g-1)/g of the gathered result
            else:
                eff = operand_b
            eff = int(eff * max(gsize - 1, 0) / max(gsize, 1))
            ops.append({"kind": kind, "computation": cname,
                        "operand_bytes": operand_b, "group_size": gsize,
                        "multiplier": mult, "effective_bytes": eff})
            totals[kind] += operand_b * mult
    eff_totals = {}
    for o in ops:
        eff_totals[o["kind"]] = eff_totals.get(o["kind"], 0) \
            + o["effective_bytes"] * o["multiplier"]
    return {"ops": ops, "per_device_bytes_by_kind": totals,
            "per_device_bytes_total": sum(totals.values()),
            "effective_bytes_by_kind": eff_totals,
            "effective_bytes_total": sum(eff_totals.values())}


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             unroll_groups: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    base = get_config(arch)
    cfg = config_for_shape(base, shape, num_shards=n_dev)
    specs = input_specs(cfg, shape)

    t0 = time.time()
    bundle = bundle_for(cfg, mesh, shape, specs, unroll_groups=unroll_groups)
    with mesh:
        jitted = jax.jit(bundle.fn,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = cost_analysis_dict(compiled)
    peak = int(ma.peak_memory_in_bytes)
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)

    return {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": peak,
        },
        "cost_analysis": {
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
        },
        "collectives": {
            "per_device_bytes_by_kind": colls["per_device_bytes_by_kind"],
            "per_device_bytes_total": colls["per_device_bytes_total"],
            "effective_bytes_by_kind": colls["effective_bytes_by_kind"],
            "effective_bytes_total": colls["effective_bytes_total"],
            "num_ops": len(colls["ops"]),
            "ops_summary": _summarize(colls["ops"]),
        },
        "full_groups": cfg.full_groups,
        "moe_groups": cfg.moe_groups,
    }


def _summarize(ops):
    agg = {}
    for o in ops:
        key = (o["kind"], o["group_size"])
        a = agg.setdefault(key, {"count": 0, "bytes": 0})
        a["count"] += 1
        a["bytes"] += o["operand_bytes"] * o["multiplier"]
    return [{"kind": k, "group_size": g, **v}
            for (k, g), v in sorted(agg.items())]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = SHAPE_NAMES if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes:
            ok, why = shape_applicable(cfg, shape)
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = out / f"{tag}.json"
                if args.skip_existing and path.exists():
                    print(f"[skip] {tag}")
                    continue
                if not ok:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "skipped": True, "reason": why}
                    path.write_text(json.dumps(rec, indent=1))
                    print(f"[n/a ] {tag}: {why}")
                    continue
                print(f"[run ] {tag} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=mp)
                    print(f"[ ok ] {tag}: compile={rec['compile_s']}s "
                          f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                          f"coll={rec['collectives']['per_device_bytes_total']/2**20:.1f}MiB",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}",
                          flush=True)
                path.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
