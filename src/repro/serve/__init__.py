"""Offload-aware serving subsystem (the paper's decision problem, online).

Instead of one offline offload decision per batch driver run
(repro.launch.serve's one-shot path), this package serves a *stream* of
generation requests:

    workload.WorkloadSpec.build  -> trace-driven request stream (Poisson /
                                    Gamma / MMPP arrivals, heavy-tail
                                    lengths, multi-turn sessions, tenant
                                    SLO classes — DESIGN.md §13)
    queue.RequestQueue           -> arrival-ordered admission bookkeeping
                                    (tenant-priority ordering under overload)
    scheduler.OffloadAwareScheduler
                                 -> Eq.-3 admission control + per-batch
                                    parallel extent M from the fitted model
    calibrator.OnlineCalibrator  -> sliding-window least-squares refit of
                                    (alpha, beta, gamma) from measured step
                                    timings — the model tracks the live
                                    system, not hardcoded coefficients
    batcher.ContinuousBatcher    -> slot-managed continuous batching with
                                    per-slot cache lengths and mid-wave
                                    admission (DESIGN.md §6); virtual
                                    open-loop clock, optional real JAX
                                    engine; pipeline=True drives the async
                                    fabric protocol — refill prefills
                                    overlap in-flight decode work on a
                                    double-buffered fabric (DESIGN.md §7)
    prefix.PrefixStore           -> per-fabric prefix-KV residency with LRU
                                    capacity: warm hits skip prefill, cold
                                    handoffs pull KV as a memcpy offload
    metrics.ServeMetrics         -> throughput / p99 latency / SLO
                                    attainment / queue delay / occupancy /
                                    goodput / prefix hit accounting
    fleet.FabricFleet            -> N independent fabrics (each with its own
                                    scaled HWParams, calibrator, scheduler)
                                    behind a model-driven Router
                                    (model|rr|lql) with an optional session
                                    affinity term — the horizontal scaling
                                    layer (DESIGN.md §8)

``serve_workload`` wires the single-fabric stack together; ``serve_fleet``
is its fleet counterpart.  Both take their knobs as one frozen config
object — ``serve_workload(spec, config=ServeConfig(...))`` /
``serve_fleet(spec, config=FleetConfig(...))`` — which is what the
``python -m repro.launch.serve`` CLI and the serving benchmarks build.  The
historical keyword-argument sprawl still works through a shim that emits a
``DeprecationWarning`` and produces byte-identical results (regression-
tested in tests/test_serve.py).
"""

from __future__ import annotations

import dataclasses
import time
import warnings

from repro.runtime.fault import FaultEvent, FaultInjector

from .batcher import ContinuousBatcher, PendingStep, ServingEngine
from .calibrator import CalibrationSnapshot, OnlineCalibrator
from .fabric import CompletedJob, SimulatedFabric, WallClockFabric
from .fleet import (RECOVERY_MODES, ROUTER_OBJECTIVES, ROUTER_POLICIES,
                    FabricFleet, FleetLane, RouteDecision, Router,
                    fabric_prior, serve_fleet)
from .metrics import FleetMetrics, ServeMetrics
from .prefix import DEFAULT_CAPACITY_TOKENS, PrefixStore
from .queue import Request, RequestQueue, RequestState
from .scheduler import AdmissionDecision, BatchPlan, OffloadAwareScheduler
from .workload import (ARRIVALS, CYCLES_PER_SECOND, LENGTH_DISTS,
                       TENANT_CLASSES, TenantClass, Workload, WORKLOADS,
                       WorkloadSpec, derive_seed, synthetic_workload,
                       workload_for)

__all__ = [
    "AdmissionDecision", "ARRIVALS", "BatchPlan", "CalibrationSnapshot",
    "CompletedJob", "ContinuousBatcher", "CYCLES_PER_SECOND",
    "DEFAULT_CAPACITY_TOKENS", "FabricFleet", "FaultEvent",
    "FaultInjector", "FleetConfig", "FleetLane", "FleetMetrics",
    "LENGTH_DISTS", "OffloadAwareScheduler",
    "OnlineCalibrator", "PendingStep", "PrefixStore", "RECOVERY_MODES",
    "Request", "RequestQueue", "RequestState", "ROUTER_OBJECTIVES",
    "ROUTER_POLICIES", "RouteDecision", "Router", "ServeConfig",
    "ServeMetrics", "ServingEngine", "SimulatedFabric", "TenantClass",
    "TENANT_CLASSES", "WallClockFabric", "Workload", "WORKLOADS",
    "WorkloadSpec", "derive_seed", "fabric_prior",
    "serve_fleet", "serve_workload", "synthetic_workload", "workload_for",
]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every knob of the single-fabric serving stack, as one frozen value.

    ``serve_workload(spec, config=ServeConfig(...))`` replaces the
    keyword-argument sprawl the entry point accreted over PRs 1–9; field
    names and defaults are exactly the historical kwargs, so
    ``dataclasses.replace`` on a default config is the migration.  The
    final block is the DESIGN.md §13 session-affinity/tenant layer — all
    default-off (bit-identity with PR 9).
    """

    arch: str = "chatglm3-6b"
    reduced: bool = True
    execute: bool = True
    max_batch: int = 4
    mesh_shape: tuple = (1, 1)
    jitter_pct: float = 1.0
    fabric: str = "simulated"
    calibrator: OnlineCalibrator | None = None
    available_m: tuple = (1, 2, 4, 8, 16, 32)
    design: object | None = None
    wave_boundary: bool = False
    pipeline: bool = False
    buffering: str | None = None
    dvfs: object = None
    tracer: object = None
    residuals: object = None
    faults: object = None
    fault_seed: int | None = None
    fused_decode: bool = False
    # --- session affinity + tenant classes (DESIGN.md §13) ---
    affinity: bool = False                      # warm-hit prefill skipping
    prefix_capacity: int = DEFAULT_CAPACITY_TOKENS
    priority: bool = False                      # tenant-class queue ordering
    preempt: bool = False                       # evict for higher classes
    shed_depth: dict | None = None              # priority -> backlog cap


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Every knob of the fleet serving stack (:func:`serve_fleet`).

    Same redesign as :class:`ServeConfig` — field names and defaults are
    the historical ``serve_fleet`` kwargs plus the DESIGN.md §13 affinity
    layer, all default-off.
    """

    fleet: tuple = (32,)                        # cluster count per fabric
    router: str = "model"
    objective: str = "latency"
    arch: str = "chatglm3-6b"
    reduced: bool = True
    execute: bool = False
    max_batch: int = 4
    mesh_shape: tuple = (1, 1)
    jitter_pct: float = 1.0
    wave_boundary: bool = False
    pipeline: bool = False
    buffering: str | None = None
    dvfs: object = None
    tracer: object = None
    residuals: object = None
    faults: object = None
    fault_seed: int | None = None
    recovery: str = "restore"
    ckpt_every: int = 4
    tie_seed: int | None = None
    # --- session affinity + tenant classes (DESIGN.md §13) ---
    affinity: bool = False                      # router affinity term + hits
    prefix_capacity: int = DEFAULT_CAPACITY_TOKENS
    priority: bool = False
    preempt: bool = False
    shed_depth: dict | None = None


def _config_from_kwargs(config, cls, kwargs: dict, fn_name: str):
    """The deprecation shim behind both serving entry points.

    Legacy keyword call sites keep working — each kwarg overrides the
    matching config field via ``dataclasses.replace``, so the result is
    byte-identical to passing the equivalent config — but they now warn:
    the config object is the API (unknown names still raise ``TypeError``,
    exactly like the old signature did).
    """
    if kwargs:
        warnings.warn(
            f"passing {fn_name}() options as keyword arguments is "
            f"deprecated; pass config={cls.__name__}(...) instead",
            DeprecationWarning, stacklevel=3)
        return dataclasses.replace(config or cls(), **kwargs)
    return config or cls()


def serve_workload(
    spec: WorkloadSpec | None = None,
    *,
    config: ServeConfig | None = None,
    **kwargs,
) -> dict:
    """Run the full serving stack on a trace-driven open-loop workload.

    All options ride in ``config`` (:class:`ServeConfig`); passing them as
    keyword arguments still works via a ``DeprecationWarning`` shim with
    byte-identical results.  Field semantics:

    ``fused_decode=True`` compiles the engine's decode step on the fused
    Pallas decode-attention kernel (one launch per layer; bit-identical
    tokens — DESIGN.md §12).  Only meaningful with ``execute=True``.

    ``faults`` attaches a :class:`repro.runtime.fault.FaultInjector` (or a
    ``--faults`` spec string) against lane 0: stalls freeze the clock, skew
    poisons the calibrator's measurement channel, and a crash halts the
    fabric — with no fleet behind this path there is nowhere to recover to,
    so crash orphans are FAILED and reported as ``dropped`` (single-fabric
    crash recovery IS the fleet, DESIGN.md §10).

    ``execute=False`` skips the real JAX engine (no tokens generated) and
    exercises only the queue/scheduler/calibrator/clock machinery — the
    pure-scheduler benchmark mode.

    ``wave_boundary=True`` disables mid-wave admission (the legacy
    iteration-level batching: requests join only at wave boundaries) — the
    A/B baseline for the continuous slot-managed loop (DESIGN.md §6).

    ``pipeline=True`` upgrades the continuous loop to the asynchronous
    fabric protocol (DESIGN.md §7): refill prefills are dispatched under
    in-flight decode work on a double-buffered fabric, hiding the offload
    constant that the sequential loop pays per refill.  ``buffering``
    overrides the fabric's descriptor depth (defaults to ``"double"`` when
    pipelining, ``"single"`` otherwise, or the design's own axis when
    serving a swept point).

    ``fabric`` picks the timing source the clock/SLOs/calibrator run on:
    ``"simulated"`` (Manticore cycle model; Eq.-1 coefficients are
    meaningful across M) or ``"wallclock"`` (the real engine's measured
    DispatchStats/CreditCounterSync step times — requires ``execute=True``;
    the calibrator then tracks the live host hardware, where M is a planning
    label rather than a physical extent).

    ``design`` serves a swept co-design point (``repro.dse.DesignPoint``)
    instead of the paper's extended design: the simulated fabric runs that
    design's hardware/dispatch/sync/kernel, and — unless an explicit
    ``calibrator`` is passed — the scheduler's prior becomes the design's own
    Eq.-1 refit rather than ``PAPER_MODEL`` (DESIGN.md §3.4).

    ``tracer`` (a :class:`repro.obs.Tracer`) records the run as structured
    spans — engine phases, request lifecycle, scheduler/calibrator decisions
    — and ``residuals`` (a :class:`repro.obs.ResidualTracker`) pairs every
    prediction with its measured outcome (DESIGN.md §9).  The trace process
    is named like a one-lane fleet's lane 0 (``f0:{clusters}c``), so a 1x32
    fleet trace is event-identical to this path modulo routing.  With
    ``fabric="wallclock"`` the tracer also records the engine's warm-up
    (``setup.warmup``) and the continuous loop's layers on the host's
    real clock (``decode.*``, ``prefill.*``).

    ``affinity=True`` attaches a :class:`PrefixStore` (DESIGN.md §13):
    admission resolves each session request's warm-hit length against the
    fabric's KV residency and prefill jobs skip the resident tokens.
    ``priority`` orders the arrived backlog by tenant class, ``preempt``
    evicts running lower classes for premium arrivals, and ``shed_depth``
    rejects over-backlog classes at admission — all default-off.

    Besides the batcher's results, the returned dict carries
    ``engine_setup_s``: the seconds spent building and warming the engine,
    compilation included (0 without ``execute``).
    """
    cfg = _config_from_kwargs(config, ServeConfig, kwargs, "serve_workload")
    spec = spec or WorkloadSpec()
    calibrator = cfg.calibrator
    buffering = cfg.buffering
    if cfg.design is not None and cfg.fabric != "simulated":
        raise ValueError("design= requires the simulated fabric")
    if buffering is None:
        buffering = (getattr(cfg.design, "buffering", None)
                     or ("double" if cfg.pipeline else "single"))
    if calibrator is None:
        if cfg.design is not None:
            from repro.dse.runner import refit_design
            prior, _ = refit_design(cfg.design, force_eq1=True)
            calibrator = OnlineCalibrator(prior=prior)
        else:
            calibrator = OnlineCalibrator()
    if cfg.fabric == "simulated":
        if cfg.design is not None:
            fabric_src = SimulatedFabric.for_design(cfg.design,
                                                    jitter_pct=cfg.jitter_pct,
                                                    seed=spec.seed)
            if buffering != fabric_src.buffering or cfg.dvfs is not None:
                fabric_src = SimulatedFabric(
                    hw=fabric_src.hw, kernel=fabric_src.kernel,
                    dispatch=fabric_src.dispatch, sync=fabric_src.sync,
                    jitter_pct=cfg.jitter_pct, seed=spec.seed,
                    buffering=buffering, dvfs=cfg.dvfs)
            # Plan host fallbacks against the design's own hardware/kernel.
            from repro.core import simulator as _sim
            host_model = lambda n: float(_sim.host_runtime(  # noqa: E731
                n, hw=fabric_src.hw, kernel=fabric_src.kernel))
        else:
            # The fabric is sized to the configured extent grid: interconnect
            # parameters scale with the cluster count (simulator.scaled_hw;
            # identity at the paper's 32-cluster reference).
            fabric_src = SimulatedFabric(jitter_pct=cfg.jitter_pct,
                                         seed=spec.seed,
                                         num_clusters=max(cfg.available_m),
                                         buffering=buffering, dvfs=cfg.dvfs)
            host_model = None  # Manticore host fallback (same cycle domain)
    elif cfg.fabric == "wallclock":
        if not cfg.execute:
            raise ValueError("fabric='wallclock' needs execute=True: the "
                             "engine's measurements are the job runtimes")
        fabric_src = WallClockFabric()
        # The engine executes every job — there is no host fallback whose
        # runtime lives in the wall-cycle domain, so never "keep on host"
        # (comparing wall cycles against simulator cycles is meaningless).
        host_model = lambda n: float("inf")  # noqa: E731
    else:
        raise ValueError(f"unknown fabric {cfg.fabric!r}")
    proc = f"f0:{max(cfg.available_m)}c"
    if cfg.tracer is not None:
        calibrator.tracer = cfg.tracer
        calibrator.proc = proc
        if isinstance(fabric_src, SimulatedFabric):
            fabric_src.proc = proc
            fabric_src.engine.tracer = cfg.tracer
            fabric_src.engine.proc = proc
    scheduler = OffloadAwareScheduler(calibrator,
                                      available_m=cfg.available_m,
                                      host_model=host_model,
                                      tracer=cfg.tracer, proc=proc,
                                      shed_depth=cfg.shed_depth)

    if cfg.execute:
        from repro.configs import get_config
        from repro.models import scaled_down
        mcfg = get_config(cfg.arch)
        if cfg.reduced:
            mcfg = scaled_down(mcfg)
        spec = dataclasses.replace(spec, vocab_size=mcfg.vocab_size)

    requests = spec.build(with_tokens=cfg.execute)

    engine = None
    setup_s = 0.0
    if cfg.execute:
        t_setup = time.perf_counter()
        # Size the decode cache from the *generated* trace, not the spec's
        # nominal length mix: multi-turn sessions carry cumulative context
        # (DESIGN.md §13.1), so a later turn's prompt can exceed
        # max(prompt_lens) by the whole conversation so far.
        max_len = max((r.prompt_len + r.gen_len for r in requests),
                      default=max(spec.prompt_lens) + max(spec.gen_lens))
        engine = ServingEngine(cfg.arch, reduced=cfg.reduced,
                               max_batch=cfg.max_batch, max_len=max_len,
                               mesh_shape=cfg.mesh_shape,
                               fused_decode=cfg.fused_decode,
                               tracer=cfg.tracer)
        if cfg.fabric == "wallclock":
            # Compile outliers must not enter the measured step times the
            # calibrator fits (see ServingEngine.warmup).  Session traces
            # realize prompt lengths beyond the spec mix, so warm the
            # lengths actually present.
            engine.warmup(sorted({r.prompt_len for r in requests}),
                          slots=not cfg.wave_boundary)
        setup_s = time.perf_counter() - t_setup
    faults = cfg.faults
    if isinstance(faults, str):
        horizon = max((r.arrival for r in requests), default=0.0)
        faults = FaultInjector.parse(
            faults, horizon=horizon, num_lanes=1,
            seed=(derive_seed(spec.seed, "faults")
                  if cfg.fault_seed is None else cfg.fault_seed))
    prefix_store = PrefixStore(cfg.prefix_capacity) if cfg.affinity else None
    batcher = ContinuousBatcher(scheduler, calibrator, fabric=fabric_src,
                                engine=engine, max_batch=cfg.max_batch,
                                wave_boundary=cfg.wave_boundary,
                                pipeline=cfg.pipeline, tracer=cfg.tracer,
                                residuals=cfg.residuals, proc=proc,
                                faults=faults, fault_lane=0,
                                prefix_store=prefix_store,
                                priority=cfg.priority, preempt=cfg.preempt)
    if engine is not None:
        batcher.metrics.decode_exchange_bytes = engine.decode_exchange_bytes
    out = batcher.run(requests)
    if out["orphans"]:
        # No fleet behind this path: a crash's orphans have nowhere to go.
        for r in out["orphans"]:
            r.state = RequestState.FAILED
            batcher.metrics.dropped += 1
        out["requests"] = sorted(out["requests"] + out["orphans"],
                                 key=lambda r: r.rid)
    out["arch"] = cfg.arch
    out["engine_setup_s"] = setup_s
    out["spec"] = spec
    out["faults"] = faults
    out["config"] = cfg
    return out
