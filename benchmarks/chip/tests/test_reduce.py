"""Trace reduction: unions, per-program sums and collective time, on
hand-made events and on a trace recorded on one TPU v5e."""

from pathlib import Path

import pytest

import chip_paths  # noqa: F401  (before the modules below)
import reduce
from reduce import MODULES_LINE, OPS_LINE, Event

DATA = Path(__file__).resolve().parent / "data"


def op(dev, name, start, dur, line=OPS_LINE):
    return Event(dev, line, name, float(start), float(dur))


def hand_events():
    """Two devices; device 0 runs a decode program (ops 0-10 and 8-14,
    overlapping) and a prefill program with an all-reduce, device 1 an
    all-gather that overlaps an all-reduce."""
    return [
        op(0, "jit_decode_fn(1)", 0, 15, MODULES_LINE),
        op(0, "%fusion.1 = bf16[8] fusion(bf16[8] %all-reduce-done.3)",
           0, 10),
        op(0, "%copy.2 = bf16[8] copy(bf16[8] %p)", 8, 6),
        op(0, "jit_slot_prefill_step(2)", 20, 30, MODULES_LINE),
        op(0, "%all-reduce.3 = f32[8] all-reduce(f32[8] %x), "
              "replica_groups={{0,1}}", 20, 5),
        op(0, "%fusion.4 = f32[8] fusion(f32[8] %all-reduce.3)", 25, 25),
        op(1, "%all-gather-start.1 = (f32[4], f32[8]) "
              "all-gather-start(f32[4] %y)", 0, 4),
        op(1, "%all-reduce.7 = f32[8] all-reduce(f32[8] %z)", 2, 4),
        op(1, "%fusion.9 = f32[8] fusion(f32[8] %all-reduce.7)", 10, 5),
        op(-1, "PjitFunction(decode_fn)", 12, 9, "main"),
    ]


def test_union_merges_overlaps_and_keeps_gaps():
    assert reduce.union([(8, 14), (0, 10), (20, 25), (25, 50)]) == \
        [(0, 14), (20, 50)]


def test_busy_is_the_union_of_op_intervals():
    ev = hand_events()
    assert reduce.busy_ns(ev, 0) == 14 + 30
    assert reduce.busy_ns(ev, 1) == 6 + 5
    assert reduce.devices(ev) == [0, 1]


def test_program_time_sums_runs_by_name():
    ev = hand_events()
    assert reduce.program_time(ev, 0, "decode_fn") == (15, 1)
    assert reduce.program_time(ev, 0, "slot_prefill_step") == (30, 1)
    assert reduce.program_time(ev, 1, "decode_fn") == (0, 0)


def test_collectives_are_found_by_opcode_not_operands():
    ev = hand_events()
    # fusion.1 reads %all-reduce-done.3 and fusion.4 %all-reduce.3: not
    # collectives.  Device 1's all-gather and all-reduce overlap on 2-4.
    assert reduce.collective_ns(ev, 0) == 5
    assert reduce.collective_ns(ev, 1) == 6
    assert reduce.opcode(ev[6].name) == "all-gather-start"


@pytest.mark.parametrize("text,want", [
    ("%all-reduce.13 = f32[64,8,4] all-reduce(f32[64,8,4] %x), "
     "replica_groups=[1,4]<=[4]", True),
    ("%all-gather-start.2 = (bf16[8], bf16[32]) all-gather-start(%y)", True),
    # The TPU compiler's async collective fusions (a decode step of the
    # four-chip granite cell, compiled for a described 2x2 v5e).
    ("%async-collective-start = (bf16[64,2,128], bf16[64,8,128]) "
     "fusion(bf16[64,2,128] %p), kind=kCustom, "
     "calls=%fused_computation.131", True),
    ("%async-collective-done = bf16[64,8,128] fusion(%g.526), "
     "kind=kCustom, calls=%fused_computation.134", True),
    ("%fusion.175 = (bf16[64,2,128], bf16[64,8,128]) fusion(%a, %b), "
     "kind=kCustom, calls=%async_collective_fusion.175", True),
    # Ops that only read a collective's result are not collectives.
    ("%fusion.1 = bf16[8] fusion(bf16[8] %all-reduce-done.3), "
     "kind=kLoop, calls=%fused_computation.1", False),
    ("%fusion.4 = f32[8] fusion(f32[8] %async-collective-done)", False),
    ("%copy-start = bf16[8] copy-start(bf16[8] %x.1)", False),
])
def test_which_ops_are_collectives(text, want):
    assert reduce.is_collective(text) is want


def test_breakdown_lists_ops_and_named_gaps():
    ev = hand_events()
    assert reduce.top_ops(ev, 0, 2) == [["fusion.4", pytest.approx(25e-9)],
                                        ["fusion.1", pytest.approx(10e-9)]]
    gaps = reduce.idle_gaps(ev, 0)
    assert gaps == [["PjitFunction(decode_fn) -> jit_slot_prefill_step(2)",
                     pytest.approx(6e-9)]]


def test_recorded_tpu_trace_matches_hand_counts():
    """Three runs of a jitted matmul on one v5e (tests/record_trace.py):
    each program run holds copy-start, copy-done and one fusion, 14 + 3 +
    11841, 13 + 3 + 11841 and 14 + 3 + 11840 ns, with gaps between them."""
    ev = reduce.load(str(DATA / "tpu_v5e_small_matmul.xplane.pb"))
    assert reduce.devices(ev) == [0]
    assert reduce.busy_ns(ev, 0) == 11858 + 11857 + 11857
    assert reduce.program_time(ev, 0, "small_matmul") == (3 * 11863, 3)
    assert reduce.collective_ns(ev, 0) == 0
    ops = reduce.top_ops(ev, 0)
    assert ops[0] == ["convolution_reduce_fusion",
                      pytest.approx((11841 * 2 + 11840) * 1e-9)]
    assert [name for name, _ in ops] == ["convolution_reduce_fusion",
                                         "copy-start", "copy-done"]


def _collective_share():
    import run
    return run.metric_reader("collective_share")


@pytest.mark.parametrize("trace,want", [
    # Four chips, 2 to 5 ms of collectives each in a 40 ms window.
    ({"window_s": 0.04, "collective_s": [0.002, 0.003, 0.004, 0.005]},
     100.0 * 0.0035 / 0.04),
    # One chip runs no collective; a run with no trace reads nothing.
    ({"window_s": 0.04, "collective_s": [0.0]}, None),
    ({}, None),
    (None, None),
])
def test_collective_share_is_the_chips_mean_over_the_window(trace, want):
    read = _collective_share()
    got = read({"trace": trace} if trace is not None else {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_collective_share_of_hand_events_per_chip():
    ev = hand_events()
    per_chip = [reduce.collective_ns(ev, d) * 1e-9 for d in
                reduce.devices(ev)]
    got = _collective_share()({"trace": {"window_s": 50e-9,
                                         "collective_s": per_chip}})
    assert got == pytest.approx(100.0 * (5 + 6) / 2 / 50)
