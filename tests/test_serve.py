"""Tests for the offload-aware serving subsystem (repro.serve)."""

import numpy as np
import pytest

from repro.core import decision
from repro.core.runtime_model import OffloadModel, PAPER_MODEL
from repro.serve import (ContinuousBatcher, OffloadAwareScheduler,
                         OnlineCalibrator, Request, ServeConfig,
                         SimulatedFabric, WorkloadSpec, serve_workload)

AVAILABLE = (1, 2, 4, 8, 16, 32)


def fresh_scheduler(**kw):
    return OffloadAwareScheduler(OnlineCalibrator(), available_m=AVAILABLE,
                                 **kw)


# --------------------------------------------------------------------------- #
# Scheduler: Eq.-3 consistency + admission control
# --------------------------------------------------------------------------- #
def test_plan_picks_m_min_consistent_extent():
    # Paper worked example: N=1024, t_max=700 -> M_min=5 -> next quantum 8.
    sched = fresh_scheduler()
    plan = sched.plan(1024, deadline=700.0)
    assert plan.offload and plan.m_min == 5 and plan.m == 8
    assert plan.m == decision.next_available_m(
        decision.m_min_for_deadline(PAPER_MODEL, 1024, 700.0), AVAILABLE)
    assert plan.t_pred <= 700.0 and not plan.slo_at_risk


def test_plan_without_deadline_keeps_tiny_jobs_on_host():
    sched = fresh_scheduler()
    tiny = sched.plan(16)
    big = sched.plan(8192)
    assert not tiny.offload and tiny.m is None
    assert big.offload and big.m == 32  # multicast model: monotone in M


def test_admission_rejects_slack_leq_zero():
    # alpha + beta*N = 623 > 600: no M can help (Eq. 3 infeasible).
    sched = fresh_scheduler()
    req = Request(rid=0, arrival=0.0, prompt_len=1024, gen_len=4,
                  slo_cycles=600.0)
    verdict = sched.admit(req)
    assert not verdict.admitted
    assert "slack" in verdict.reason


def test_admission_rejects_beyond_fabric_limit():
    # Feasible mathematically but needs more clusters than the fabric has.
    sched = fresh_scheduler()
    req = Request(rid=0, arrival=0.0, prompt_len=1024, gen_len=4,
                  slo_cycles=628.0)
    assert decision.m_min_for_deadline(PAPER_MODEL, 1024, 628.0) > 32
    verdict = sched.admit(req)
    assert not verdict.admitted
    assert "clusters" in verdict.reason


def test_admission_accepts_feasible_deadline():
    sched = fresh_scheduler()
    req = Request(rid=0, arrival=0.0, prompt_len=1024, gen_len=4,
                  slo_cycles=700.0)
    verdict = sched.admit(req)
    assert verdict.admitted and verdict.m_min == 5


# --------------------------------------------------------------------------- #
# Calibrator: online least-squares refit
# --------------------------------------------------------------------------- #
def _observe_grid(cal, truth, noise_pct=0.0, seed=0):
    rng = np.random.default_rng(seed)
    for m in (1, 2, 4, 8, 16, 32):
        for n in (256, 512, 768, 1024):
            t = float(truth.predict(m, n))
            if noise_pct:
                t *= 1.0 + rng.normal(0.0, noise_pct / 100.0)
            cal.observe(m, n, t)


def test_calibrator_converges_to_known_coefficients():
    truth = OffloadModel(alpha=400.0, beta=0.3, gamma=0.5)
    cal = OnlineCalibrator(prior=PAPER_MODEL, min_samples=12,
                           refit_interval=4)
    _observe_grid(cal, truth)
    snap = cal.snapshot()
    assert snap.source == "fitted"
    assert abs(snap.alpha - 400.0) < 1e-6
    assert abs(snap.beta - 0.3) < 1e-9
    assert abs(snap.gamma - 0.5) < 1e-9
    assert snap.window_mape_pct < 1e-6


def test_calibrator_converges_under_noise():
    truth = OffloadModel(alpha=400.0, beta=0.3, gamma=0.5)
    cal = OnlineCalibrator(prior=PAPER_MODEL, min_samples=12,
                           refit_interval=4)
    _observe_grid(cal, truth, noise_pct=1.0)
    snap = cal.snapshot()
    assert snap.source == "fitted"
    assert abs(snap.alpha - 400.0) / 400.0 < 0.05
    assert snap.window_mape_pct <= 5.0


def test_calibrator_pins_single_m_window_when_prior_drifts():
    """A single M makes the (1, N, N/M) design rank-deficient: the full
    fit is never attempted.  Once the prior drifts past the Eq.-2 bar the
    pinned fallback engages — level and at-M slope refit from the window,
    gamma inherited from the prior — and is exact at the pinned extent."""
    truth = OffloadModel(alpha=400.0, beta=0.3, gamma=0.5)
    cal = OnlineCalibrator(prior=PAPER_MODEL, min_samples=4,
                           refit_interval=1)
    for n in (256, 512, 768, 1024, 2048, 4096):
        cal.observe(8, n, float(truth.predict(8, n)))
    snap = cal.snapshot()
    assert snap.source == "pinned"
    assert snap.gamma == PAPER_MODEL.gamma        # inherited, not fitted
    assert snap.window_mape_pct < 1e-9
    # The at-M slope absorbs the gamma misfit: predictions at the pinned
    # extent are exact even at job sizes the window never saw.
    for n in (37, 300, 5000):
        assert float(cal.model.predict(8, n)) == \
            pytest.approx(float(truth.predict(8, n)))


def test_calibrator_keeps_healthy_prior_on_single_m_window():
    """Pinning is a drift fallback, not an optimization: a prior inside
    the Eq.-2 bar keeps serving without M diversity."""
    rng = np.random.default_rng(0)
    cal = OnlineCalibrator(prior=PAPER_MODEL, min_samples=4,
                           refit_interval=1)
    for n in (256, 512, 768, 1024, 2048, 4096):
        t = float(PAPER_MODEL.predict(8, n)) * (1 + rng.normal(0.0, 0.005))
        cal.observe(8, n, t)
    assert cal.snapshot().source == "prior"
    assert cal.model is PAPER_MODEL


def test_calibrator_upgrades_pinned_fit_once_window_diversifies():
    """M diversity arriving after a pinned fit unlocks the full refit,
    which recovers the true cross-extent coefficients."""
    truth = OffloadModel(alpha=400.0, beta=0.3, gamma=0.5)
    cal = OnlineCalibrator(prior=PAPER_MODEL, min_samples=4,
                           refit_interval=1)
    for n in (256, 512, 768, 1024):
        cal.observe(8, n, float(truth.predict(8, n)))
    assert cal.snapshot().source == "pinned"
    _observe_grid(cal, truth)
    snap = cal.snapshot()
    assert snap.source == "fitted"
    assert snap.gamma == pytest.approx(0.5)


def test_calibrator_sliding_window_tracks_drift():
    old = OffloadModel(alpha=400.0, beta=0.3, gamma=0.5)
    new = OffloadModel(alpha=800.0, beta=0.6, gamma=1.0)
    cal = OnlineCalibrator(prior=PAPER_MODEL, window=24, min_samples=12,
                           refit_interval=4)
    _observe_grid(cal, old)
    _observe_grid(cal, new)   # evicts every old sample (window=24)
    snap = cal.snapshot()
    assert abs(snap.alpha - 800.0) < 1e-6
    assert abs(snap.gamma - 1.0) < 1e-9


# --------------------------------------------------------------------------- #
# Workload generator
# --------------------------------------------------------------------------- #
def test_workload_deterministic_and_mixed():
    spec = WorkloadSpec(num_requests=64, seed=3)
    a = spec.build()
    b = spec.build()
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert [r.slo_cycles for r in a] == [r.slo_cycles for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert len({r.prompt_len for r in a}) > 1
    arr = np.array([r.arrival for r in a])
    assert (np.diff(arr) > 0).all()  # strictly increasing arrivals
    # Some requests carry deadlines; some of those are infeasible by design.
    with_slo = [r for r in a if r.slo_cycles is not None]
    assert with_slo
    infeasible = [
        r for r in with_slo
        if decision.m_min_for_deadline(PAPER_MODEL, r.prompt_len,
                                       r.slo_cycles, m_max=32) is None]
    assert infeasible


# --------------------------------------------------------------------------- #
# End-to-end (dry: no JAX engine)
# --------------------------------------------------------------------------- #
def test_dry_serving_loop_end_to_end():
    out = serve_workload(WorkloadSpec(num_requests=80, seed=11), config=ServeConfig(
              execute=False))
    m = out["metrics"]
    assert m.completed + m.rejected == m.submitted == 80
    assert m.rejected > 0                       # admission control fired
    snap = out["calibration"]
    assert snap.source == "fitted"
    assert snap.window_mape_pct <= 5.0          # acceptance criterion
    # Every non-at-risk prefill plan with a deadline is Eq.-3 consistent.
    checked = 0
    for p in out["plans"]:
        if p.kind == "prefill" and p.deadline and not p.slo_at_risk:
            assert p.m >= p.m_min and p.m in AVAILABLE
            checked += 1
    assert checked > 0
    # Rejected requests were never scheduled.
    rejected_ids = {r.rid for r in out["requests"]
                    if r.reject_reason is not None}
    finished_ids = {r.rid for r in out["requests"] if r.t_done is not None}
    assert rejected_ids.isdisjoint(finished_ids)


def test_batcher_respects_wave_deadline_feasibility():
    """Batched job size must stay feasible for the tightest member SLO."""
    cal = OnlineCalibrator()
    sched = OffloadAwareScheduler(cal, available_m=AVAILABLE)
    fabric = SimulatedFabric(jitter_pct=0.0)
    batcher = ContinuousBatcher(sched, cal, fabric=fabric, max_batch=8)
    # Four simultaneous requests; deadline only feasible for N <= ~2048.
    t_max = float(PAPER_MODEL.predict(32, 2048))
    reqs = [Request(rid=i, arrival=0.0, prompt_len=1024, gen_len=1,
                    slo_cycles=t_max) for i in range(4)]
    out = batcher.run(reqs)
    for p in out["plans"]:
        if p.kind == "prefill":
            assert not p.slo_at_risk
            assert p.n_elems <= 2048    # waves capped at 2 requests


# --------------------------------------------------------------------------- #
# End-to-end (real engine): batcher preserves per-request outputs
# --------------------------------------------------------------------------- #
@pytest.mark.slow
def test_batcher_matches_one_shot_serve():
    import jax
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import scaled_down
    from repro.serve import ServingEngine

    arch, prompts, prompt_len, gen = "chatglm3-6b", 2, 8, 4
    one_shot = serve(arch, reduced=True, prompts=prompts,
                     prompt_len=prompt_len, gen=gen)

    cfg = scaled_down(get_config(arch))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(1), (prompts, prompt_len), 0, cfg.vocab_size,
        dtype="int32"))  # the one-shot driver's prompt batch

    cal = OnlineCalibrator()
    sched = OffloadAwareScheduler(cal, available_m=AVAILABLE)
    engine = ServingEngine(arch, reduced=True, max_batch=prompts,
                           max_len=prompt_len + gen)
    batcher = ContinuousBatcher(sched, cal,
                                fabric=SimulatedFabric(jitter_pct=0.0),
                                engine=engine)
    reqs = [Request(rid=i, arrival=0.0, prompt_len=prompt_len, gen_len=gen,
                    tokens=tokens[i]) for i in range(prompts)]
    out = batcher.run(reqs)

    assert out["metrics"].waves == 1  # both fit one wave: same batching
    got = np.stack([r.generated for r in out["requests"]])
    np.testing.assert_array_equal(got, one_shot["generated"])


# --------------------------------------------------------------------------- #
# Continuous batching (per-slot lengths + mid-wave admission) — DESIGN.md §6
# --------------------------------------------------------------------------- #
STRAGGLER_SPEC = WorkloadSpec(num_requests=256, rate_rps=2e6,
                              gen_lens=(4, 16, 64), seed=7)


def test_midwave_admission_beats_wave_boundary_on_same_trace():
    """The acceptance A/B: same Poisson trace, higher rps + no worse p99."""
    wave = serve_workload(STRAGGLER_SPEC, config=ServeConfig(
               execute=False, wave_boundary=True))
    cont = serve_workload(STRAGGLER_SPEC, config=ServeConfig(execute=False))
    ws, cs = wave["metrics"].summary(), cont["metrics"].summary()
    assert cs["throughput_rps"] > ws["throughput_rps"]
    assert cs["latency_us"]["p99"] <= ws["latency_us"]["p99"]
    # The win comes from actually refilling slots mid-wave.
    assert cs["mid_wave_admissions"] > 0
    assert ws["mid_wave_admissions"] == 0
    assert cs["slot_occupancy"]["mean"] > ws["slot_occupancy"]["mean"]
    # Same trace, same admission decisions, same completion set.
    def outcome(out):
        return {r.rid: r.reject_reason is not None for r in out["requests"]}
    assert outcome(wave) == outcome(cont)
    assert ws["completed"] == cs["completed"]


def test_continuous_metrics_series_and_goodput():
    out = serve_workload(WorkloadSpec(num_requests=64, seed=11), config=ServeConfig(
              execute=False))
    m = out["metrics"]
    # One queue-delay sample per served request; delays are non-negative.
    assert len(m.queue_delay_cycles) == m.completed
    assert all(x >= 0 for x in m.queue_delay_cycles.series())
    # Occupancy is a per-decode-job series in (0, 1].
    assert len(m.slot_occupancy) > 0
    assert all(0 < x <= 1 for x in m.slot_occupancy.series())
    # Every completed request emitted exactly gen_len tokens.
    done = [r for r in out["requests"] if r.t_done is not None]
    assert m.tokens_generated == sum(r.gen_len for r in done)
    # Goodput counts completions that met their SLO or carried none.
    expect_good = sum(1 for r in done if r.slo_met is not False)
    assert m.goodput_completed == expect_good <= m.completed
    s = m.summary()
    assert s["goodput_rps"] <= s["throughput_rps"]


def test_wave_boundary_flag_reproduces_legacy_wave_metrics():
    out = serve_workload(WorkloadSpec(num_requests=80, seed=11), config=ServeConfig(
              execute=False, wave_boundary=True))
    m = out["metrics"]
    assert m.completed + m.rejected == m.submitted == 80
    assert m.mid_wave_admissions == 0
    snap = out["calibration"]
    assert snap.source == "fitted"
    assert snap.window_mape_pct <= 5.0


class _StubEngine:
    """Engine double: fixed wall time per step, deterministic tokens.

    Mimics the ServingEngine surface the batcher uses, without JAX — the
    point is that the *executed* batch is always the padded ``max_batch``
    rows, which is what WallClockFabric measurements correspond to.
    """

    def __init__(self, max_batch=4):
        self.max_batch = max_batch

    def init_caches(self):
        return {}

    def prefill(self, tokens, metrics=None):
        return np.zeros(self.max_batch, np.int32), {}, 1e-6

    def prefill_into_slots(self, tokens, caches, mask, metrics=None):
        return np.zeros(self.max_batch, np.int32), caches, 1e-6

    def decode(self, tok, caches, lens):
        return np.zeros(self.max_batch, np.int32), caches, 1e-6


@pytest.mark.parametrize("wave_boundary", [False, True])
def test_wallclock_calibration_uses_executed_batch_size(wave_boundary):
    """Regression: decode jobs are *planned* with the occupied-slot count
    but *executed* with the padded max_batch rows — WallClockFabric samples
    must carry the executed N, or the calibrator ingests mismatched (N, t)
    pairs (prefill likewise: max_batch * prompt_len)."""
    from repro.serve import WallClockFabric

    max_batch, prompt_len = 4, 16
    cal = OnlineCalibrator()
    # host_model=inf: every job offloads, so every job feeds the calibrator.
    sched = OffloadAwareScheduler(cal, available_m=AVAILABLE,
                                  host_model=lambda n: float("inf"))
    engine = _StubEngine(max_batch)
    batcher = ContinuousBatcher(sched, cal, fabric=WallClockFabric(),
                                engine=engine, wave_boundary=wave_boundary)
    reqs = [Request(rid=i, arrival=float(i), prompt_len=prompt_len,
                    gen_len=g, tokens=np.zeros(prompt_len, np.int32))
            for i, g in enumerate((1, 3, 5))]
    out = batcher.run(reqs)
    assert out["metrics"].completed == 3
    samples = list(cal._samples)
    assert samples, "offloaded jobs must feed the calibrator"
    decode_plans = [p for p in out["plans"] if p.kind == "decode"]
    # The loop really did plan decode jobs below the full batch...
    assert any(p.n_elems < max_batch for p in decode_plans)
    # ...but every wall-clock calibration sample carries the executed size.
    n_prefills = sum(1 for p in out["plans"] if p.kind == "prefill")
    expect = {max_batch, max_batch * prompt_len}
    assert {n for _, n, _ in samples} <= expect
    assert sum(1 for _, n, _ in samples
               if n == max_batch * prompt_len) == n_prefills


def test_simulated_fabric_calibration_uses_planned_job_size():
    """With the simulated fabric the measurement IS the planned job, so
    samples keep the occupied-slot N (no padding correction)."""
    cal = OnlineCalibrator()
    sched = OffloadAwareScheduler(cal, available_m=AVAILABLE,
                                  host_model=lambda n: float("inf"))
    batcher = ContinuousBatcher(sched, cal,
                                fabric=SimulatedFabric(jitter_pct=0.0),
                                max_batch=4)
    reqs = [Request(rid=i, arrival=0.0, prompt_len=16, gen_len=g)
            for i, g in enumerate((1, 3, 5))]
    out = batcher.run(reqs)
    decode_ns = {p.n_elems for p in out["plans"] if p.kind == "decode"}
    sample_ns = {n for _, n, _ in cal._samples}
    assert decode_ns <= sample_ns  # planned == observed job sizes


# --------------------------------------------------------------------------- #
# Pipelined serving (async fabric protocol) — DESIGN.md §7
# --------------------------------------------------------------------------- #
def test_pipelined_beats_midwave_on_same_trace():
    """The tentpole A/B: hiding refill-prefill dispatch/sync under in-flight
    decode work buys throughput on top of mid-wave admission."""
    cont = serve_workload(STRAGGLER_SPEC, config=ServeConfig(execute=False))
    pipe = serve_workload(STRAGGLER_SPEC, config=ServeConfig(
               execute=False, pipeline=True))
    cs, ps = cont["metrics"].summary(), pipe["metrics"].summary()
    assert ps["throughput_rps"] > cs["throughput_rps"]
    assert ps["latency_us"]["p99"] <= cs["latency_us"]["p99"]
    # The win comes from jobs actually overlapping on the engine timeline.
    assert ps["pipeline"]["pipelined_prefills"] > 0
    assert ps["pipeline"]["overlap_total_cycles"] > 0
    # Same trace, same admission decisions, same completion set.
    def outcome(out):
        return {r.rid: r.reject_reason is not None for r in out["requests"]}
    assert outcome(cont) == outcome(pipe)
    assert cs["completed"] == ps["completed"]


def test_pipelined_calibration_stays_under_2pct_mape():
    out = serve_workload(STRAGGLER_SPEC, config=ServeConfig(
              execute=False, pipeline=True))
    snap = out["calibration"]
    assert snap.source == "fitted"
    assert snap.window_mape_pct is not None and snap.window_mape_pct <= 2.0


def test_pipelined_metrics_overlap_and_bubble_series():
    out = serve_workload(WorkloadSpec(num_requests=64, seed=11), config=ServeConfig(
              execute=False, pipeline=True))
    m = out["metrics"]
    # One overlap/bubble point per job (prefills + decodes).
    assert len(m.overlap_cycles) == len(out["plans"])
    assert len(m.bubble_cycles) == len(out["plans"])
    assert all(x >= 0 for x in m.overlap_cycles.series())
    assert m.pipelined_prefills > 0
    s = m.summary()
    assert s["pipeline"]["overlap_total_cycles"] == pytest.approx(
        m.overlap_cycles.total())
    assert "pipeline:" in m.format_summary()


def test_sequential_modes_record_no_overlap_series():
    out = serve_workload(WorkloadSpec(num_requests=16, seed=3), config=ServeConfig(
              execute=False))
    m = out["metrics"]
    assert len(m.overlap_cycles) == 0 and m.pipelined_prefills == 0
    assert "pipeline:" not in m.format_summary()


def test_pipeline_and_wave_boundary_are_exclusive():
    cal = OnlineCalibrator()
    sched = OffloadAwareScheduler(cal, available_m=AVAILABLE)
    with pytest.raises(ValueError):
        ContinuousBatcher(sched, cal, pipeline=True, wave_boundary=True)


def test_simulated_fabric_async_protocol_roundtrip():
    fab = SimulatedFabric(jitter_pct=0.0, buffering="double")
    h1 = fab.submit(32, 1024, t_submit=0.0)
    h2 = fab.submit(32, 1024, t_submit=0.0)
    assert not fab.ready(h1, h1.t_done - 1) and fab.ready(h1, h1.t_done)
    j1, j2 = fab.complete(h1), fab.complete(h2)
    assert j1.total == fab.offload(32, 1024)  # jitter off: closed form
    assert j2.overlap > 0                      # dispatch hid under exec of j1
    assert j2.t_done - j1.t_done < j1.total    # back-to-back beats blocking


def test_wallclock_fabric_async_needs_measurement():
    from repro.serve import WallClockFabric
    fab = WallClockFabric()
    h = fab.submit(4, 128, t_submit=100.0)
    with pytest.raises(RuntimeError):
        fab.complete(h)
    job = fab.complete(h, wall_s=1e-6)
    assert job.total == pytest.approx(1000.0)  # 1 us at 1 GHz
    assert job.t_done == pytest.approx(1100.0)


@pytest.mark.slow
def test_pipelined_tokens_match_continuous_with_real_engine():
    """Acceptance: mixed prefill/decode in-flight jobs produce bit-identical
    tokens to the sequential slot-managed path (real engine)."""
    from repro.serve import ServingEngine

    arch = "chatglm3-6b"
    rng = np.random.default_rng(5)
    spec = [(8, 5, 0.0), (4, 3, 0.0), (8, 2, 1500.0), (4, 6, 3000.0),
            (8, 4, 9000.0)]
    prompts = {i: rng.integers(0, 128, size=(pl,), dtype=np.int32)
               for i, (pl, _, _) in enumerate(spec)}

    def run(pipeline):
        engine = ServingEngine(arch, reduced=True, max_batch=3, max_len=16)
        cal = OnlineCalibrator()
        sched = OffloadAwareScheduler(cal, available_m=AVAILABLE)
        fabric = SimulatedFabric(jitter_pct=0.0,
                                 buffering="double" if pipeline else "single")
        b = ContinuousBatcher(sched, cal, fabric=fabric, engine=engine,
                              pipeline=pipeline)
        reqs = [Request(rid=i, arrival=arr, prompt_len=pl, gen_len=g,
                        tokens=prompts[i])
                for i, (pl, g, arr) in enumerate(spec)]
        return b.run(reqs)

    cont, pipe = run(False), run(True)
    assert pipe["metrics"].pipelined_prefills > 0  # prefills really in flight
    for rc, rp in zip(cont["requests"], pipe["requests"]):
        assert rc.rid == rp.rid
        np.testing.assert_array_equal(rc.generated, rp.generated)


@pytest.mark.slow
def test_continuous_mixed_length_slots_match_wave_boundary_tokens():
    """Acceptance: mixed-length slots produce identical tokens to the
    wave-boundary path for the same requests (real engine)."""
    from repro.serve import ServingEngine

    arch = "chatglm3-6b"
    rng = np.random.default_rng(5)
    spec = [(8, 5, 0.0), (4, 3, 0.0), (8, 2, 1500.0), (4, 6, 3000.0),
            (8, 4, 9000.0)]
    prompts = {i: rng.integers(0, 128, size=(pl,), dtype=np.int32)
               for i, (pl, _, _) in enumerate(spec)}

    def run(wave_boundary):
        engine = ServingEngine(arch, reduced=True, max_batch=3, max_len=16)
        cal = OnlineCalibrator()
        sched = OffloadAwareScheduler(cal, available_m=AVAILABLE)
        b = ContinuousBatcher(sched, cal,
                              fabric=SimulatedFabric(jitter_pct=0.0),
                              engine=engine, wave_boundary=wave_boundary)
        reqs = [Request(rid=i, arrival=arr, prompt_len=pl, gen_len=g,
                        tokens=prompts[i])
                for i, (pl, g, arr) in enumerate(spec)]
        return b.run(reqs)

    wave, cont = run(True), run(False)
    assert cont["metrics"].mid_wave_admissions > 0  # slots really mixed
    for rw, rc in zip(wave["requests"], cont["requests"]):
        assert rw.rid == rc.rid
        np.testing.assert_array_equal(rw.generated, rc.generated)


@pytest.mark.parametrize("argv,reduced", [
    ([], True), (["--reduced"], True), (["--no-reduced"], False)],
    ids=["default", "reduced", "no-reduced"])
def test_cli_reduced_flag_reaches_serve_config(argv, reduced):
    """``--no-reduced`` is how the CLI reaches the published widths."""
    from repro.launch.serve import main
    out = main(["--no-execute", "--requests", "4", "--arch", "chatglm3-6b",
                *argv])
    assert out["config"].reduced is reduced


def test_engine_params_are_eager_init_params_in_place():
    """The engine draws its weights under jit, straight into the decode
    step's parameter shardings, with the values eager init_params gives."""
    import jax
    from repro.configs import get_config
    from repro.models import init_params, scaled_down
    from repro.serve import ServingEngine
    eng = ServingEngine("chatglm3-6b", reduced=True, max_batch=2, max_len=16,
                        param_seed=3)
    want = init_params(jax.random.key(3), scaled_down(get_config("chatglm3-6b")))
    got_leaves, want_leaves = jax.tree.leaves(eng.params), jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves)
    for got, ref in zip(got_leaves, want_leaves):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
