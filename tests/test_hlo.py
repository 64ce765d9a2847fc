"""The HLO collective census (``repro.runtime.hlo``) on canned HLO text: a
scanned all-reduce, an async ``all-reduce-start``/``-done`` pair, a TPU
async collective fusion (one all-gather cloned into its start,
continuation and done computations) and a plain all-gather; and its
import, which leaves the environment alone."""

import textwrap

import pytest

from repro.runtime.hlo import parse_collectives

#: One scan of 40 trips whose body holds a plain all-reduce and an async
#: collective fusion, and, outside it, an async all-reduce pair and a plain
#: all-gather.  The fusion's all-gather (channel 9) appears in three
#: computations, as the TPU compiler writes it.
HLO = textwrap.dedent("""
    HloModule decode

    %add (a: bf16[], b: bf16[]) -> bf16[] {
      ROOT %r = bf16[] add(bf16[] %a, bf16[] %b)
    }

    %ag_start (p0: bf16[64,2,128]) -> (bf16[64,2,128], bf16[64,8,128], u32[]) {
      %p0 = bf16[64,2,128]{2,0,1:T(8,128)(2,1)S(1)} parameter(0)
      %all-gather.26 = bf16[64,8,128]{2,0,1:T(8,128)(2,1)} all-gather(%p0), channel_id=9, replica_groups=[1,4]<=[4], dimensions={1}
      ROOT %cc = (bf16[64,2,128]{2,0,1}, bf16[64,8,128]{2,0,1}, u32[]) custom-call(%all-gather.26), custom_call_target="AsyncCollectiveStart"
    }

    %async_collective_fusion.171 (p1: bf16[64,2,128], p2: bf16[64,8,128]) -> (bf16[64,8,128], u32[]) {
      %p1 = bf16[64,2,128]{2,0,1} parameter(0)
      %all-gather.28 = bf16[64,8,128]{2,0,1:T(8,128)(2,1)S(1)} all-gather(%p1), channel_id=9, replica_groups=[1,4]<=[4], dimensions={1}
      ROOT %t = (bf16[64,8,128]{2,0,1}, u32[]) tuple(%all-gather.28, %flag)
    }

    %ag_done (p3: bf16[64,2,128], p4: bf16[64,8,128]) -> bf16[64,8,128] {
      %p3 = bf16[64,2,128]{2,0,1} parameter(0)
      %all-gather.32 = bf16[64,8,128]{2,0,1:T(8,128)(2,1)} all-gather(%p3), channel_id=9, replica_groups=[1,4]<=[4], dimensions={1}
      ROOT %cd = bf16[64,8,128]{2,0,1} custom-call(%p3, %p4, %all-gather.32), custom_call_target="AsyncCollectiveDone"
    }

    %cond (s: (s32[], bf16[64,1,4096])) -> pred[] {
      %c = s32[]{:T(128)} constant(40)
      %i = s32[] get-tuple-element((s32[], bf16[64,1,4096]) %s), index=0
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    %body (s: (s32[], bf16[64,1,4096])) -> (s32[], bf16[64,1,4096]) {
      %x = bf16[64,1,4096]{2,0,1} get-tuple-element(%s), index=1
      %async-collective-start = (bf16[64,2,128]{2,0,1}, bf16[64,8,128]{2,0,1}, /*index=2*/u32[]) fusion(%k), kind=kCustom, calls=%ag_start
      %fusion.171 = (bf16[64,8,128]{2,0,1}, u32[]) fusion(%k, %g), kind=kOutput, calls=%async_collective_fusion.171
      %async-collective-done = bf16[64,8,128]{2,0,1} fusion(%k, %g), kind=kCustom, calls=%ag_done
      %all-reduce.16 = bf16[64,1,4096]{2,0,1:T(8,128)(2,1)S(1)} all-reduce(%x), channel_id=10, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add
      ROOT %t = (s32[], bf16[64,1,4096]) tuple(%i2, %all-reduce.16)
    }

    ENTRY %main (p: bf16[64,1,4096]) -> f32[4,64] {
      %w = (s32[], bf16[64,1,4096]) while(%init), condition=%cond, body=%body
      %ars = bf16[64,1,4096]{2,0,1} all-reduce-start(%e), channel_id=12, replica_groups=[1,4]<=[4], to_apply=%add
      %ard = bf16[64,1,4096]{2,0,1} all-reduce-done(%ars)
      %all-gather.5 = f32[4,64]{1,0:T(4,128)} all-gather(%m), channel_id=15, replica_groups=[1,4]<=[4], dimensions={0}
      ROOT %out = f32[4,64] copy(%all-gather.5)
    }
""")

ROW = 64 * 4096 * 2          # bf16[64,1,4096]: 512 KiB


def _ops(out, kind):
    return [o for o in out["ops"] if o["kind"] == kind]


def test_every_form_is_counted_once():
    out = parse_collectives(HLO)
    assert len(out["ops"]) == 4
    assert len(_ops(out, "all-reduce")) == 2
    assert len(_ops(out, "all-gather")) == 2


def test_scanned_ops_are_weighted_by_the_trip_count():
    out = parse_collectives(HLO)
    # The plain all-reduce in the scan body, and the async fusion's
    # all-gather, reached from the body through ``calls=``.
    scanned = [o for o in out["ops"] if o["multiplier"] == 40]
    assert {(o["kind"], o["operand_bytes"]) for o in scanned} == {
        ("all-reduce", ROW), ("all-gather", 64 * 2 * 128 * 2)}
    top = [o for o in out["ops"] if o["multiplier"] == 1]
    assert {(o["kind"], o["operand_bytes"]) for o in top} == {
        ("all-reduce", ROW), ("all-gather", 4 * 64 * 4 // 4)}


def test_totals_are_operand_bytes_times_runs():
    out = parse_collectives(HLO)
    assert out["per_device_bytes_by_kind"]["all-reduce"] == 40 * ROW + ROW
    assert out["per_device_bytes_by_kind"]["all-gather"] == (
        40 * 64 * 2 * 128 * 2 + 4 * 64 * 4 // 4)
    assert out["per_device_bytes_total"] == sum(
        out["per_device_bytes_by_kind"].values())
    # Ring model: an all-reduce moves 2 (g - 1) / g of its operand.
    ar = _ops(out, "all-reduce")[0]
    assert ar["group_size"] == 4
    assert ar["effective_bytes"] == int(2 * ROW * 3 / 4)


@pytest.mark.parametrize("start", [
    "%ags = (f32[8]{0}, f32[32]{0}) all-gather-start(%x), channel_id=3, "
    "replica_groups=[1,4]<=[4], dimensions={0}",
    "%ags = f32[32]{0} all-gather(f32[8]{0} %x), channel_id=3, "
    "replica_groups=[1,4]<=[4], dimensions={0}"],
    ids=["async", "plain"])
def test_an_async_start_reads_as_its_plain_op(start):
    hlo = f"ENTRY %main (x: f32[8]) -> f32[32] {{\n  {start}\n" \
          "  ROOT %agd = f32[32]{0} all-gather-done(%ags)\n}\n"
    (op,) = parse_collectives(hlo)["ops"]
    assert (op["kind"], op["operand_bytes"], op["group_size"]) == (
        "all-gather", 32, 4)


def test_importing_it_leaves_xla_flags_alone():
    from conftest import run_py
    r = run_py("""
import os
before = os.environ.get("XLA_FLAGS")
import repro.runtime.hlo
assert os.environ.get("XLA_FLAGS") == before, os.environ.get("XLA_FLAGS")
print("OK")
""", env_extra={"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"})
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.parametrize("line,kind,operand", [
    ("%ar = (f32[8]{0}, f32[16]{0}) all-reduce(%a, %b), channel_id=4, "
     "replica_groups=[1,4]<=[4], to_apply=%add", "all-reduce", 96),
    ("%ars = (f32[8]{0}, f32[16]{0}) all-reduce-start(%a, %b), "
     "channel_id=4, replica_groups=[1,4]<=[4], to_apply=%add",
     "all-reduce", 96),
    ("%a2a = (bf16[4,32]{1,0}, bf16[4,32]{1,0}) all-to-all(%a, %b), "
     "channel_id=4, replica_groups=[1,2]<=[2]", "all-to-all", 512)],
    ids=["all_reduce", "all_reduce_start", "all_to_all"])
def test_a_combined_collective_counts_all_its_arrays(line, kind, operand):
    hlo = f"ENTRY %main (a: f32[8], b: f32[16]) -> f32[8] {{\n  {line}\n}}\n"
    (op,) = parse_collectives(hlo)["ops"]
    assert (op["kind"], op["operand_bytes"]) == (kind, operand)
