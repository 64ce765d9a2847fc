"""Low-overhead structured tracer for the offload serving stack.

The paper's claim is that offloaded runtime can be *modeled* (Eq. 1, ≤1%
MAPE); PRs 4-5 plan against that model at three layers (engine phase
timelines, Eq.-3 scheduler, fleet router).  This module is the observation
side: a span/instant/counter event recorder threaded through the engine,
batcher, scheduler, calibrator, and router, so every prediction the system
acts on can later be laid next to what actually happened (DESIGN.md §9).

Event model
-----------

Events live on **tracks**: a ``(proc, track)`` pair, where ``proc`` groups
the tracks of one component (a fabric lane like ``"f0:32c"``, or the
``"router"``) and ``track`` names one serial resource or event stream inside
it (``"host"``, ``"fabric"``, ``"sync"``, ``"jobs"``, ``"requests"``, ...).
The Chrome-trace exporter (repro.obs.export) maps procs to processes and
tracks to threads, so Perfetto renders one swim-lane per resource.

Three event shapes:

  * ``span(...)``   — a complete interval (Chrome phase ``"X"``): engine
    dispatch/exec/sync phases, batcher jobs, request queue residency;
  * ``instant(...)``— a point event (``"i"``): admissions, route decisions,
    calibrator refits, residual observations;
  * ``counter(...)``— a sampled value (``"C"``): slot occupancy, queue depth.

``flow_start``/``flow_end`` emit Chrome flow events (``"s"``/``"f"``) that
visually link a route decision to the prefill execution it caused; the flow
id is the request id.

Two time domains (DESIGN.md §9): ``domain="cycles"`` is the fabric-cycle
virtual clock the scheduler plans in (at the paper's 1 GHz, cycles == ns);
``domain="wall_s"`` is the host's ``time.perf_counter()`` in seconds, for
the served path on a real engine.  :meth:`Tracer.wall` records such a span
around a block of host code, and a :class:`WallLane` records consecutive
ones (a serving loop's layers): each has a real start, a real end, and,
while it is open, a ``jax.profiler.TraceAnnotation`` of the same name, so
that under a profiler session every wall span is also a host event of the
device trace, on the profiler's own clock.  The exporter keeps the domains
in separate process groups: the cycle clock shares no epoch with the
host's.

Overhead budget: tracing defaults to **off** — every instrumentation site
guards with ``if tracer is not None`` (or holds the shared :data:`NULL`
no-op whose methods return immediately, and whose lane is a shared no-op
too), so the disabled cost is one attribute check or one no-op call per
event site and the benchmark headlines stay inside the
``tools/bench_compare.py`` gate.  Enabled cost is one dataclass append per
event (plus a clock read and a profiler annotation per wall span);
exporters do all formatting after the run.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

#: The tracer's two time domains (DESIGN.md §9).
TIME_DOMAINS = ("cycles", "wall_s")


@dataclass
class TraceEvent:
    """One recorded event; exporters translate to Chrome/JSONL records."""

    ph: str                    # "X" span | "i" instant | "C" counter
    #                          # | "s"/"f" flow start/end
    name: str
    proc: str                  # process-level track group (e.g. a lane)
    track: str                 # serial resource / stream within the proc
    ts: float                  # start time in the event's domain
    dur: float = 0.0           # span length ("X" only)
    domain: str = "cycles"     # "cycles" | "wall_s"
    args: dict | None = None   # payload shown in the Perfetto side panel
    flow: int | None = None    # flow id ("s"/"f" only; request rid)

    def as_dict(self) -> dict:
        d = {"ph": self.ph, "name": self.name, "proc": self.proc,
             "track": self.track, "ts": self.ts, "domain": self.domain}
        if self.ph == "X":
            d["dur"] = self.dur
        if self.args:
            d["args"] = self.args
        if self.flow is not None:
            d["flow"] = self.flow
        return d


class Tracer:
    """In-memory structured event recorder (spans + instants + counters)."""

    enabled = True

    def __init__(self):
        self.events: list[TraceEvent] = []

    def __bool__(self) -> bool:  # ``if tracer:`` guards stay truthy
        return True

    def __len__(self) -> int:
        return len(self.events)

    # ------------------------------------------------------------------ #
    def span(self, proc: str, track: str, name: str, ts: float, dur: float,
             *, domain: str = "cycles", args: dict | None = None) -> None:
        """A complete interval [ts, ts+dur) on one track."""
        self.events.append(TraceEvent("X", name, proc, track, ts, dur,
                                      domain, args))

    def instant(self, proc: str, track: str, name: str, ts: float, *,
                domain: str = "cycles", args: dict | None = None) -> None:
        self.events.append(TraceEvent("i", name, proc, track, ts, 0.0,
                                      domain, args))

    def counter(self, proc: str, track: str, name: str, ts: float,
                value: float, *, domain: str = "cycles") -> None:
        self.events.append(TraceEvent("C", name, proc, track, ts, 0.0,
                                      domain, {"value": float(value)}))

    def flow_start(self, proc: str, track: str, name: str, ts: float,
                   flow: int, *, domain: str = "cycles") -> None:
        """Open a flow arrow (e.g. a route decision); close with
        :meth:`flow_end` under the same ``flow`` id."""
        self.events.append(TraceEvent("s", name, proc, track, ts, 0.0,
                                      domain, None, flow))

    def flow_end(self, proc: str, track: str, name: str, ts: float,
                 flow: int, *, domain: str = "cycles") -> None:
        self.events.append(TraceEvent("f", name, proc, track, ts, 0.0,
                                      domain, None, flow))

    def annotation(self, name: str):
        """A ``jax.profiler.TraceAnnotation``: while it is open, a running
        profiler session records a host event ``name`` on its own clock."""
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def lane(self, proc: str) -> "WallLane":
        """A :class:`WallLane`: consecutive ``wall_s`` spans on ``proc``."""
        return WallLane(self, proc)

    @contextlib.contextmanager
    def wall(self, proc: str, track: str, name: str,
             args: dict | None = None):
        """Context manager: the block it wraps as one ``wall_s`` span,
        from ``time.perf_counter()`` on entry to the same on exit, with the
        block inside :meth:`annotation` of the same name."""
        lane = WallLane(self, proc)
        lane.mark(track, name, args)
        try:
            yield
        finally:
            lane.close()

    # ------------------------------------------------------------------ #
    def lane_events(self, proc: str) -> list[tuple]:
        """Comparable event tuples of one proc, flow linkage excluded.

        The fleet identity tests use this: a 1x32 fleet lane must be
        event-identical to the single-fabric path *modulo the routing
        layer* — the router proc and the flow binds it injects are the only
        legitimate difference (DESIGN.md §9).
        """
        return [
            (e.ph, e.name, e.track, e.ts, e.dur, e.domain,
             tuple(sorted(e.args.items())) if e.args else None)
            for e in self.events
            if e.proc == proc and e.ph not in ("s", "f")
        ]

    def procs(self) -> list[str]:
        seen: dict[str, None] = {}
        for e in self.events:
            seen.setdefault(e.proc)
        return list(seen)


class WallLane:
    """Consecutive ``wall_s`` spans on one proc, such as a serving loop's
    layers.

    :meth:`mark` ends the open span and starts the next at one
    ``time.perf_counter()`` instant, so the spans tile the code between
    marks with no time left out; :meth:`close` ends the open span.  While a
    span is open, its :meth:`Tracer.annotation` is open too.  ``args`` is
    stored by reference: the code under a span may still fill it in.
    """

    __slots__ = ("tracer", "proc", "_open")

    def __init__(self, tracer: Tracer, proc: str):
        self.tracer, self.proc = tracer, proc
        self._open = None      # (track, name, args, start, annotation)

    def mark(self, track: str, name: str, args: dict | None = None) -> None:
        # The profiler stamps an annotation when it is made: read the clock
        # right after, before any other work of the mark.
        ann = self.tracer.annotation(name)
        t = time.perf_counter()
        self._end(t)
        ann.__enter__()
        self._open = (track, name, args, t, ann)

    def close(self) -> None:
        self._end(time.perf_counter())

    def _end(self, t: float) -> None:
        if self._open is None:
            return
        track, name, args, t0, ann = self._open
        self._open = None
        ann.__exit__(None, None, None)
        self.tracer.events.append(TraceEvent("X", name, self.proc, track, t0,
                                             t - t0, "wall_s", args))


class _NullLane:
    """The lane of :class:`NullTracer`: records nothing."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def mark(self, *a, **k) -> None:
        pass

    def close(self) -> None:
        pass


#: The one no-op lane (stateless, shared).
NULL_LANE = _NullLane()

#: The one no-op context :class:`NullTracer` hands out (reusable).
_NULL_CONTEXT = contextlib.nullcontext()


class NullTracer:
    """Zero-cost default: every method is a no-op and ``bool()`` is False,
    so hot paths may either call through or skip with ``if tracer:``."""

    enabled = False
    events: list = []

    def __bool__(self) -> bool:
        return False

    def __len__(self) -> int:
        return 0

    def span(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def counter(self, *a, **k) -> None:
        pass

    def flow_start(self, *a, **k) -> None:
        pass

    def flow_end(self, *a, **k) -> None:
        pass

    def lane(self, proc: str) -> _NullLane:
        return NULL_LANE

    def wall(self, *a, **k) -> contextlib.nullcontext:
        return _NULL_CONTEXT


#: Shared no-op instance — components store this when no tracer is attached.
NULL = NullTracer()
