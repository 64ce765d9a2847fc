"""Benchmark aggregator: one section per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run --fast          # skip measured
  PYTHONPATH=src python -m benchmarks.run --json BENCH.json
  PYTHONPATH=src python -m benchmarks.run --fast --smoke  # CI smoke tier

``--json`` additionally writes machine-readable results — a flat list of
{section, name, value, unit} records — so the perf trajectory can be
tracked across PRs (BENCH_*.json files diffed by CI or by hand).

``--smoke`` shrinks the serving traces to tiny extents AND asserts the
headline results (paper speedups, refit MAPEs, mid-wave and pipelined
serving gains) so a benchmark regression fails the CI build instead of
rotting silently.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def section(title):
    print(f"\n===== {title} =====", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip subprocess-measured benches")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny extents + assert headline results "
                         "(the CI regression gate)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write machine-readable records to PATH")
    args = ap.parse_args(argv)
    from repro.runtime.flags import enable_compile_cache
    enable_compile_cache()

    records: list[dict] = []

    def rec(section_name, name, value, unit):
        records.append({"section": section_name, "name": name,
                        "value": float(value), "unit": unit})

    t0 = time.time()
    section("Fig. 1 left — DAXPY runtime vs clusters (cycles)")
    from benchmarks import fig1_left
    for m, tb, tm in fig1_left.main():
        rec("fig1_left", f"speedup_m{m}", tb / tm, "x")

    section("Fig. 1 right — speedup grid (multicast/credit vs baseline)")
    from benchmarks import fig1_right
    g = fig1_right.main()
    best = max(g.values())
    rec("fig1_right", "max_speedup", best, "x")
    rec("fig1_right", "mean_speedup", sum(g.values()) / len(g), "x")

    section("Eq. 2 — runtime-model MAPE per problem size (%)")
    from benchmarks import mape_table
    t = mape_table.main()
    for label in ("paper_eq1", "fitted"):
        worst = max(t[label].values())
        rec("eq2_mape", f"{label}_worst", worst, "pct")

    section("Offload decision (Eq. 3) — M_min under deadline")
    from repro.core import decision
    from repro.core.runtime_model import PAPER_MODEL
    from repro.core.simulator import host_runtime
    print("n,t_max_cycles,m_min,m_selected,feasible")
    for n, t_max in [(256, 500), (1024, 700), (1024, 640), (4096, 1500),
                     (4096, 1400)]:
        rep = decision.deadline_report(PAPER_MODEL, n, t_max,
                                       [1, 2, 4, 8, 16, 32])
        print(f"{n},{t_max},{rep['m_min_raw']},{rep['m_selected']},"
              f"{rep['feasible']}")
    print("n,host_cycles,best_offload_cycles,decision")
    for n in (16, 64, 256, 1024, 8192):
        d = decision.should_offload(PAPER_MODEL, host_runtime, n,
                                    [1, 2, 4, 8, 16, 32])
        print(f"{n},{d.t_host:.0f},{d.t_offload:.0f},"
              f"{'offload(M=%d)' % d.m if d.offload else 'host'}")
    n_star = decision.breakeven_n(PAPER_MODEL, host_runtime,
                                  [1, 2, 4, 8, 16, 32])
    rec("eq3_decision", "breakeven_n", n_star, "elems")

    section("Event engine (repro.core.engine) — pipelined offload streams")
    from repro.core import simulator as sim
    from repro.core.engine import steady_runtime
    from repro.core.runtime_model import fit_pipelined_from_engine
    single = sim.offload_runtime(32, 4096, multicast=True)
    steady = steady_runtime(32, 4096)
    print(f"back-to-back DAXPY at (M=32, N=4096): {single} cy isolated -> "
          f"{steady:.0f} cy steady-state ({single / steady:.2f}x)")
    rec("engine", "steady_speedup_32x4096", single / steady, "x")
    eff_model, eff_mape = fit_pipelined_from_engine()
    print(f"overlap-aware refit: {eff_model} (MAPE {eff_mape:.2f}%) — "
          f"alpha_eff vs closed-form 367")
    rec("engine", "alpha_eff", eff_model.alpha, "cycles")
    rec("engine", "alpha_eff_mape", eff_mape, "pct")

    section("Co-design explorer (repro.dse) — design-space sweep + refits")
    from benchmarks import dse_sweep
    records += dse_sweep.main(fast=args.fast)

    section("Serving scheduler (repro.serve) — open-loop synthetic workload")
    from benchmarks import serve_scheduler
    records += serve_scheduler.main(fast=args.fast, smoke=args.smoke)

    section("Fleet router (repro.serve.fleet) — heterogeneous multi-fabric "
            "A/B + composition sweep")
    from benchmarks import fleet_router
    records += fleet_router.main(fast=args.fast, smoke=args.smoke)

    section("Fault tolerance (repro.serve + repro.runtime.fault) — "
            "kill-a-fabric recovery A/B")
    from benchmarks import fault_tolerance
    records += fault_tolerance.main(fast=args.fast, smoke=args.smoke)

    section("Overload A/B (repro.serve, DESIGN.md §13) — session affinity "
            "+ tenant classes under 2x overload")
    from benchmarks import overload_ab
    records += overload_ab.main(fast=args.fast, smoke=args.smoke)

    if not args.fast:
        section("Measured dispatch/sync scaling on host CPU devices (us)")
        from benchmarks import dispatch_microbench
        dispatch_microbench.main()

    section("Roofline (single-pod) — analytic cell costs "
            "(+ dry-run artifacts when present)")
    from benchmarks import roofline_report
    dryrun_dir = Path("results/dryrun")
    rows = roofline_report.analyze(dryrun_dir)
    print(roofline_report.to_markdown(rows))
    if not dryrun_dir.exists():
        print("(results/dryrun missing — analytic terms only; for measured "
              "artifacts run: python -m repro.launch.dryrun --all "
              "--mesh both)")
    records += roofline_report.records(rows)

    section("Fused decode attention (kernels/decode_attention.py) — "
            "numerics + Eq.-1 view + engine A/B")
    records += roofline_report.decode_attention_records()

    total = time.time() - t0
    rec("run", "total_seconds", total, "s")
    print(f"\n(total {total:.1f}s)")

    if args.json:
        Path(args.json).write_text(json.dumps(records, indent=2) + "\n")
        print(f"wrote {len(records)} records to {args.json}")

    if args.smoke:
        _smoke_gate(records)


def _smoke_gate(records: list[dict]) -> None:
    """Assert the headline results; a regression fails the CI build."""
    by_name = {r["name"]: r["value"] for r in records}
    checks = [
        # Paper reproduction: the 47.9% co-design speedup survives.
        ("fig1_right max_speedup", by_name["max_speedup"] >= 1.4),
        # Eq.-2 model quality: both fits within the paper's MAPE bar.
        ("eq2 paper_eq1 MAPE", by_name["paper_eq1_worst"] <= 2.0),
        ("eq2 fitted MAPE", by_name["fitted_worst"] <= 2.0),
        # Overlap-aware effective-alpha fit (DESIGN.md §7.2).
        ("alpha_eff collapse", by_name["alpha_eff"] <= 100.0),
        ("alpha_eff MAPE", by_name["alpha_eff_mape"] <= 2.0),
        # Serving A/B: each loop upgrade keeps its throughput win.
        ("midwave > wave", by_name["midwave_throughput_gain"] > 0.0),
        ("pipelined > midwave",
         by_name["pipe_vs_midwave_throughput_gain"] > 0.0),
        # Calibration tracks the pipelined trace within the 2% bar.  The
        # record is -1.0 when the calibrator never produced a fitted window
        # — that is a failure, not a pass, hence the lower bound.
        ("pipelined calib MAPE", 0.0 <= by_name["pipe_calib_mape"] <= 2.0),
        # Fleet A/B (DESIGN.md §8): model-driven routing beats round-robin
        # on the heterogeneous big+little fleet on BOTH headline metrics.
        ("fleet model > rr throughput",
         by_name["fleet_model_vs_rr_throughput_gain"] > 0.0),
        ("fleet model <= rr p99",
         by_name["fleet_model_vs_rr_p99_delta"] <= 0.0),
        # A homogeneous one-fabric fleet reproduces the single-fabric
        # pipelined serving numbers exactly (the fleet layer composes the
        # existing machinery — it must not perturb it).
        ("fleet 1x32 == single fabric",
         by_name["fleet_single_identity"] == 1.0),
        # Every per-fabric online calibration stays inside the Eq.-2 bar.
        ("fleet calib MAPE",
         0.0 <= by_name["fleet_model_calib_mape_max"] <= 2.0),
        # Energy accounting (DESIGN.md §11).  The calibrated energy twin
        # tracks the fabric's closed-form joules inside the same Eq.-2 bar.
        ("fleet energy calib MAPE",
         0.0 <= by_name["fleet_energy_calib_mape_max"] <= 2.0),
        # Per joule, the little fabrics out-serve the big one — the
        # efficiency asymmetry the energy/edp router objectives exploit.
        ("fleet little > big tokens/joule",
         by_name["fleet_little_big_tpj_ratio"] > 1.0),
        # Leaving DVFS unset prices exactly the nominal operating point:
        # the energy axis is inert on the default path (bit-identical).
        ("energy defaults inert",
         by_name["energy_default_zero_delta"] == 0.0),
        # The roofline's energy-per-element view exists and is positive.
        ("roofline energy per element",
         by_name["energy_pj_per_flop_best"] > 0.0),
        # Fused decode attention (kernels/decode_attention.py, DESIGN.md
        # §12).  The kernel must match the unfused composition (V-cache
        # bit-exact, K/out within a few ULP), greedy tokens must be
        # bit-identical through the engine, and the Eq.-1 priced gain of
        # one launch over three must never dip below parity — the
        # deterministic form of the fused-throughput headline (wallclock
        # interpret-mode timings are informational, not gated).
        ("fused decode numerics", by_name["decode_attn_numerics_ok"] == 1.0),
        ("fused decode token identity",
         by_name["decode_attn_token_identity"] == 1.0),
        ("fused decode sim gain >= 1",
         by_name["decode_attn_fused_sim_gain_x"] >= 1.0),
        ("fused decode sim gain (long ctx) >= 1",
         by_name["decode_attn_fused_sim_gain_long_x"] >= 1.0),
        # The registered decode_attention KernelSpec stays representable
        # by one Eq.-1 alpha/beta/gamma model within the paper's bar, both
        # standalone and as refit by the DSE sweep, and the fused design
        # survives to the (runtime, cost) Pareto front.
        ("fused decode Eq.-1 MAPE",
         0.0 <= by_name["decode_attn_eq1_mape"] <= 2.0),
        ("fused decode DSE refit MAPE",
         0.0 <= by_name["decode_attention_refit_mape_pct"] <= 2.0),
        ("fused decode on DSE front",
         by_name["decode_attention_on_front"] == 1.0),
        # The fused-design serving run's online calibrator tracks its own
        # Eq.-1 prior inside the paper's bar (serve_scheduler 'fused_*').
        ("fused serve calib MAPE",
         0.0 <= by_name["fused_calib_mape"] <= 2.0),
        # Fault tolerance (DESIGN.md §10): recovery buys goodput back after
        # a mid-serve fabric crash, and must beat the naive-drop baseline.
        ("ft recovery attainment >= 0.9",
         by_name["ft_recovery_attainment"] >= 0.9),
        ("ft recovery > naive drop",
         by_name["ft_recovery_attainment"] > by_name["ft_drop_attainment"]),
        # Blast-radius containment: every completion that predates crash
        # detection is bit-identical to the fault-free run.
        ("ft unaffected identity",
         by_name["ft_unaffected_identity"] == 1.0),
        # The checkpoint-restore path is genuinely exercised (>= 1 Eq.-1
        # priced KV restore), not bypassed by all-queued orphans.
        ("ft restore exercised", by_name["ft_restore_jobs"] >= 1.0),
        # Overload A/B (DESIGN.md §13): session affinity must STRICTLY
        # dominate the affinity-off arm on both headline metrics of the
        # bursty multi-tenant trace — goodput AND p99 latency.  Re-sent
        # conversation context is real work; skipping it must show up.
        ("overload affinity > off goodput",
         by_name["overload_affinity_goodput"]
         > by_name["overload_noaff_goodput"]),
        ("overload affinity < off p99",
         by_name["overload_affinity_p99_us"]
         < by_name["overload_noaff_p99_us"]),
        # The affinity machinery is genuinely exercised: at least half of
        # the session lookups land a warm prefix hit.
        ("overload affinity hit rate >= 0.5",
         by_name["overload_affinity_hit_rate"] >= 0.5),
        # Graceful degradation: under 2x overload the premium class still
        # completes >= 90% of its traffic (shed falls on lower classes).
        ("overload premium attainment >= 0.9",
         by_name["overload_premium_attainment"] >= 0.9),
        # API redesign invariant: the deprecated kwarg shim reproduces the
        # config-object run byte-identically (affinity off on both sides).
        ("overload kwarg-shim identity",
         by_name["overload_affinity_off_identity"] == 1.0),
    ]
    failed = [name for name, ok in checks if not ok]
    print(f"smoke gate: {len(checks) - len(failed)}/{len(checks)} checks ok")
    if failed:
        raise SystemExit("smoke gate FAILED: " + ", ".join(failed))


if __name__ == "__main__":
    main()
