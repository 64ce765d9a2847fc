"""Readings that set a cell's limit on the served tokens' logit gap.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [--first-seed <k>]

In one process, on the chips the cell asks for, serves a short job of the
cell for each of ``--seeds`` seeds, exactly as a benchmark run does (same
entry point, batch, lengths and sample of checked requests), and judges on
the same rows both the program and the fp8 control put in its place (the
reference computed with float8 weights, its own first choice at each
position scored against the float32 reference), each by the harness's own
``judge``.  Prints one JSON line per seed with both verdicts and their
numbers, and a summary: the program's largest gap (the limit's lower end),
the control's smallest (its upper end), and whether every program run was
correct and every control run not.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)

    bench = run.load_benchmark()
    entry = run.find_cell(bench, args.workload)
    cfg = run.work.load_config(entry["config"])
    mix = run.traffic.load_mix(entry["traffic"])
    cell = run.load_cell(args.workload)
    if not run.chip_ready(entry, cfg):
        return 2
    served, control, verdicts = [], [], []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        res = run.run(args.workload, seed=seed, seconds=args.seconds,
                      trace=False, cfg=cfg, mix=mix, cell=cell, bench=bench,
                      control=True,
                      log=lambda s: print(s, file=sys.stderr, flush=True))
        ctl = res["control"]
        served.append(res["checks"]["logit_gap"]["value"])
        control.append(ctl["checks"]["logit_gap"]["value"])
        verdicts.append((res["correct"], ctl["correct"]))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "failed": res["failed"], "checks": res["checks"],
                          "control_correct": ctl["correct"],
                          "control_checks": ctl["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "served_max": max(served), "served": served,
                      "control_min": min(control), "control": control,
                      "program_correct_every_seed":
                          all(p for p, _ in verdicts),
                      "control_correct_on_no_seed":
                          not any(c for _, c in verdicts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
