"""Collective census of a compiled program's HLO text.

``parse_collectives(compiled.as_text())`` lists each collective the
partitioner put in (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute) with its per-device operand bytes, its replica-group
size and the number of times one run of the program executes it: a scan
body appears once in the HLO but runs trip-count times, so each op is
weighted by the trip counts of the while loops around it, through any
fusion or call that holds it.

It reads the forms the compilers emit:
  * plain ops, ``%ar = f32[64]{0} all-reduce(%x), channel_id=1, ...``;
  * async pairs, ``all-reduce-start`` / ``all-reduce-done`` (and the other
    kinds'): the ``-start`` op is the collective, the ``-done`` is not
    counted again;
  * the TPU compiler's async collective fusions: one collective split over
    a start fusion, continuation fusions (``async_collective_fusion``
    computations) and a done fusion, each computation holding a clone of
    the op under the same ``channel_id``.  A collective is counted once
    per channel.

Importing this module touches no JAX state and no environment variable.
"""

from __future__ import annotations

import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_ARRAY = r"[a-z0-9]+\[[\d,]*\]"
_COMP = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{$")
_WHILE = re.compile(r"while\(.*\).*condition=%?([\w\.\-]+),"
                    r"\s*body=%?([\w\.\-]+)")
_CALLS = re.compile(r"\b(?:calls|to_apply|condition|body)=%?([\w\.\-]+)")
_OP = re.compile(r"=\s*(" + _ARRAY + r"|\((?:[^()]|\([^()]*\))*\))[^=]*?\b("
                 + "|".join(COLLECTIVES) + r")(-start)?\(")
_GROUP = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")


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


def _computations(hlo: str) -> dict[str, list[str]]:
    """Instruction lines of each computation, by name.  A header may have
    tuple parameters with nested parens: the greedy ``\\(.*\\)`` takes
    them."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = _COMP.match(line.strip())
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int | None:
    """A while loop's trip count: the largest constant of its condition."""
    best = None
    for ln in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ln):
            v = int(m.group(1))
            best = v if best is None else max(best, v)
    return best


def _multipliers(comps: dict[str, list[str]]) -> dict[str, int]:
    """How many times one run of the program runs each computation's
    instructions: the product of the trip counts of the while bodies on
    the way up from it through its callers."""
    caller: dict[str, tuple[str, int]] = {}   # callee -> (caller, trips)
    for cname, lines in comps.items():
        for ln in lines:
            m = _WHILE.search(ln)
            if m:
                caller[m.group(2)] = (cname,
                                      _trip_count(comps.get(m.group(1), []))
                                      or 1)
            for callee in _CALLS.findall(ln):
                caller.setdefault(callee, (cname, 1))

    def mult(comp: str) -> int:
        out, seen = 1, set()
        while comp in caller and comp not in seen:
            seen.add(comp)
            comp, trips = caller[comp]
            out *= trips
        return out

    return {c: mult(c) for c in comps}


def _operand_result(type_str: str, kind: str, start: bool,
                    gsize: int) -> tuple[int, int]:
    """(operand, result) bytes per device of one collective."""
    parts = re.findall(_ARRAY, type_str)
    if start and kind != "all-reduce" and type_str.startswith("("):
        # An async start returns (operand, result, ...context).
        operand_b = _shape_bytes(parts[0]) if parts else 0
        result_b = _shape_bytes(parts[1]) if len(parts) > 1 else operand_b
        return operand_b, result_b
    # A tuple result is a combined (variadic) collective: its arrays add.
    result_b = sum(_shape_bytes(p) for p in parts)
    if kind == "all-gather":
        return result_b // max(gsize, 1), result_b
    if kind == "reduce-scatter":
        return result_b * gsize, result_b
    return result_b, result_b


def parse_collectives(hlo: str) -> dict:
    """Per-computation collective census with while-loop trip multipliers.

    Returns ``ops`` (one record per collective: kind, computation, operand
    bytes, group size, multiplier, ring-model wire bytes), the per-device
    operand bytes of one run by kind and in total
    (``per_device_bytes_by_kind``, ``per_device_bytes_total``), and the
    ring-model wire bytes likewise (``effective_bytes_*``)."""
    comps = _computations(hlo)
    mults = _multipliers(comps)
    ops = []
    totals = {k: 0.0 for k in COLLECTIVES}
    channels: set[str] = set()
    for cname, lines in comps.items():
        mult = mults[cname]
        for ln in lines:
            m = _OP.search(ln)
            if not m:
                continue
            ch = _CHANNEL.search(ln)
            if ch:
                if ch.group(1) in channels:
                    continue
                channels.add(ch.group(1))
            type_str, kind, start = m.groups()
            gm = _GROUP.search(ln)
            gsize = int(gm.group(2)) if gm else 1
            operand_b, result_b = _operand_result(type_str, kind,
                                                  bool(start), gsize)
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
    eff_totals: dict[str, int] = {}
    for o in ops:
        eff_totals[o["kind"]] = eff_totals.get(o["kind"], 0) \
            + o["effective_bytes"] * o["multiplier"]
    return {"ops": ops, "per_device_bytes_by_kind": totals,
            "per_device_bytes_total": sum(totals.values()),
            "effective_bytes_by_kind": eff_totals,
            "effective_bytes_total": sum(eff_totals.values())}
