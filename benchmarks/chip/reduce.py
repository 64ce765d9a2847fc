"""Reduce a profiler trace (``.xplane.pb``) to device time.

The loader keeps what the metrics need from the trace: on every TPU
device plane, the ``XLA Ops`` line (each operation the device ran) and the
``XLA Modules`` line (each run of a compiled program), and the host
threads' events, which name what the host was doing.  The reductions are
plain functions of those events, so a test can check them on a recorded
trace and on hand-made events alike:

  * :func:`busy_ns`: the union of a device's op intervals;
  * :func:`program_time`: the time and runs of each compiled program whose
    name holds a given fragment (``decode_fn``, ``slot_prefill_step``);
  * :func:`collective_ns`: the union of the collective ops' intervals;
  * :func:`top_ops` and :func:`idle_gaps`: the ``breakdown`` of a result.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

#: Device planes of the TPU runtime, one per chip.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Opcodes of the ops that move data between chips.
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")
#: An op event is named by its HLO text: ``%name = <type> opcode(...)``.
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)(?: = (?P<rest>.*))?$", re.DOTALL)
_OPCODE = re.compile(r"(?<![A-Za-z0-9_.-])([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """The instruction name of an op event (``fusion.12``)."""
    m = _HLO.match(text)
    return m.group("name") if m else text


def opcode(text: str) -> str:
    """The HLO opcode of an op event (``all-reduce-start``); the name
    itself where the event carries no HLO text."""
    m = _HLO.match(text)
    if m and m.group("rest"):
        op = _OPCODE.search(m.group("rest"))
        if op:
            return op.group(1)
    return op_name(text)


@dataclass(frozen=True)
class Event:
    device: int            # TPU index; -1 for host threads
    line: str              # "XLA Ops", "XLA Modules", or the host thread
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(path: str) -> list[Event]:
    """The device ops, device programs and host events of one trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith("/host:")
        if not (m or host):
            continue
        dev = int(m.group(1)) if m else -1
        for line in plane.lines:
            if m and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if e.duration_ns > 0:
                    events.append(Event(dev, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events


def devices(events) -> list[int]:
    return sorted({e.device for e in events if e.device >= 0})


def _select(events, device, line):
    return [e for e in events if e.device == device and e.line == line]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events, device: int) -> float:
    """Nanoseconds in which some op ran on ``device``."""
    ops = _select(events, device, OPS_LINE)
    return sum(e - s for s, e in union((o.start_ns, o.end_ns) for o in ops))


def program_time(events, device: int, fragment: str) -> tuple[float, int]:
    """(nanoseconds, runs) of the programs on ``device`` whose name holds
    ``fragment``."""
    runs = [e for e in _select(events, device, MODULES_LINE)
            if fragment in e.name]
    return sum(e.dur_ns for e in runs), len(runs)


def collective_ns(events, device: int) -> float:
    """Nanoseconds in which a collective op ran on ``device``."""
    ops = [o for o in _select(events, device, OPS_LINE)
           if COLLECTIVE.match(opcode(o.name))]
    return sum(e - s for s, e in union((o.start_ns, o.end_ns) for o in ops))


def top_ops(events, device: int, n: int = 10) -> list[list]:
    """The ``n`` ops with the most device time, [name, seconds]; an op
    that runs once per layer counts all its runs."""
    total: dict[str, float] = defaultdict(float)
    for o in _select(events, device, OPS_LINE):
        total[op_name(o.name)] += o.dur_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(events, device: int, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between ops on ``device`` inside the traced
    span, each named by the host event that overlaps it most and the
    device program that follows it: [name, seconds]."""
    busy = union((o.start_ns, o.end_ns)
                 for o in _select(events, device, OPS_LINE))
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events if e.device < 0]
    progs = sorted(_select(events, device, MODULES_LINE),
                   key=lambda e: e.start_ns)
    out = []
    for s, e in gaps[:n]:
        best, overlap, best_dur = "no host event", 0.0, float("inf")
        for h in host:
            o = min(e, h.end_ns) - max(s, h.start_ns)
            # Ties go to the shorter, more specific event.
            if o > overlap or (o == overlap > 0 and h.dur_ns < best_dur):
                best, overlap, best_dur = h.name, o, h.dur_ns
        nxt = next((p.name for p in progs if p.end_ns >= e), "end")
        out.append([f"{best} -> {nxt}", (e - s) * 1e-9])
    return out
