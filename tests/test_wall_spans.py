"""Real-clock program spans of the served path (DESIGN.md §9.1).

A reduced ``serve_workload(fabric="wallclock")`` with a ``Tracer``, on the
CPU, runs under ``jax.profiler``: the continuous loop's spans at each layer
boundary (``decode.*``, ``prefill.*``), the credit waits (``decode``,
``prefill``) and set-up's ``setup.warmup`` carry ``time.perf_counter()``
starts, and each one is also a host event of the same name in the
profiler's trace, on the profiler's clock.  The job's requests stamp the
host clock (``benchmarks/chip/traffic.py``), so the spans can be laid
against the job's own admissions and completions.
"""

import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip" / "tests"))
import chip_paths  # noqa: E402,F401  (before the modules below)
import reduce  # noqa: E402
import traffic  # noqa: E402
from smallrun import MIX, small_run  # noqa: E402

from repro.obs import NULL, Tracer, to_chrome  # noqa: E402
from repro.serve import ServeConfig, serve_workload  # noqa: E402

LANE = "f0:32c"
DECODE = ("decode.plan", "decode.dispatch", "decode", "decode.pull",
          "decode.account", "decode.retire")
PREFILL = ("prefill.admit", "prefill.dispatch", "prefill", "prefill.pull",
           "prefill.account", "prefill.place")


def _serve(tracer, requests=10, seed=2**33 + 7):
    """The reduced job, its requests stamped on the host clock; returns
    (output, host-clock start and end of the call)."""
    _, Spec = traffic.stamped_types()
    spec = Spec(num_requests=requests, seed=seed, mix=json.dumps(MIX))
    t0 = time.perf_counter()
    out = serve_workload(spec, config=ServeConfig(
        arch="chatglm3-6b", reduced=True, execute=True, fabric="wallclock",
        max_batch=4, tracer=tracer))
    return out, t0, time.perf_counter()


def _profile(log_dir):
    """Start the profiler as the benchmark's harness does: host events,
    no Python function tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _xplane_events(log_dir):
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    return [e for e in reduce.load(str(files[-1])) if e.device < 0]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced job under the profiler: (tracer, output, start, end,
    host events of the profiler's trace)."""
    import jax
    log_dir = tmp_path_factory.mktemp("xplane")
    tracer = Tracer()
    _profile(log_dir)
    try:
        out, t0, t1 = _serve(tracer)
    finally:
        jax.profiler.stop_trace()
    return tracer, out, t0, t1, _xplane_events(log_dir)


def _wall(tracer, proc=LANE):
    return sorted((e for e in tracer.events
                   if e.domain == "wall_s" and e.proc == proc),
                  key=lambda e: e.ts)


def test_tracer_wall_and_lane_record_real_spans_and_null_records_nothing():
    tr = Tracer()
    args = {}
    t0 = time.perf_counter()
    with tr.wall("p", "setup", "setup.warmup", args):
        args["prompt_len"] = 8            # filled in inside the block
        time.sleep(0.002)
    lane = tr.lane("p")
    lane.mark("loop", "decode.plan")
    time.sleep(0.001)
    lane.mark("engine", "decode.dispatch", {"rows": 3})
    lane.close()
    lane.close()                          # nothing open: records nothing
    t1 = time.perf_counter()
    w, plan, dispatch = tr.events
    assert (w.ph, w.name, w.proc, w.track, w.domain) == \
        ("X", "setup.warmup", "p", "setup", "wall_s")
    assert t0 <= w.ts and w.dur >= 0.002 and w.args == {"prompt_len": 8}
    assert w.ts + w.dur <= plan.ts and plan.dur >= 0.001
    assert plan.ts + plan.dur == pytest.approx(dispatch.ts, abs=1e-12)
    assert (dispatch.track, dispatch.args) == ("engine", {"rows": 3})
    assert dispatch.ts + dispatch.dur <= t1
    lane = NULL.lane("p")
    assert not lane
    lane.mark("loop", "decode.plan")
    lane.close()
    with NULL.wall("p", "setup", "setup.warmup", {"prompt_len": 8}):
        pass
    assert NULL.events == []


def test_each_decode_step_records_its_spans_in_order(traced):
    tracer, out, t0, t1 = traced[:4]
    spans = [e for e in _wall(tracer) if e.name in DECODE]
    steps = len([e for e in spans if e.name == "decode"])
    assert steps > 0
    assert [e.name for e in spans] == list(DECODE) * steps
    for a, b in zip(spans, spans[1:]):
        assert a.ts + a.dur <= b.ts + 1e-9     # in order, no overlap
    assert t0 <= spans[0].ts and spans[-1].ts + spans[-1].dur <= t1
    for e in spans:
        assert e.args == {"rows": e.args["rows"]} and \
            1 <= e.args["rows"] <= 4
    # A prefill's spans come in order, and all carry its batch.
    pre = [e for e in _wall(tracer) if e.name in PREFILL]
    n = len([e for e in pre if e.name == "prefill"])
    assert n > 0 and [e.name for e in pre] == list(PREFILL) * n
    for k in range(n):
        group = pre[6 * k:6 * k + 6]
        assert len({tuple(e.args["rids"]) for e in group}) == 1


def test_decode_spans_count_the_steps_and_warmup_records_only_setup(traced):
    tracer, out = traced[:2]
    m = out["metrics"]
    wall = _wall(tracer)
    names = [e.name for e in wall]
    assert names.count("decode") == m.decode_jobs
    assert names.count("prefill") == m.prefill_jobs
    # The waits keep their durations: the engine's measured step seconds.
    waits = [e.dur for e in wall if e.name in ("decode", "prefill")]
    assert waits == m.step_wall_s.series()
    setup = [e for e in tracer.events
             if e.domain == "wall_s" and e.proc != LANE]
    lengths = sorted({r.prompt_len for r in out["requests"]})
    assert [e.name for e in setup] == ["setup.warmup"] * len(lengths)
    assert [e.args["prompt_len"] for e in setup] == lengths
    assert setup[-1].ts + setup[-1].dur <= wall[0].ts


def test_program_spans_cover_the_job(traced):
    tracer, out = traced[:2]
    stamps = [r.wall for r in out["requests"]]
    start = min(w["t_admitted"] for w in stamps)
    end = max(w["t_done"] for w in stamps)
    covered = reduce.union(
        (max(e.ts, start), min(e.ts + e.dur, end))
        for e in _wall(tracer) if e.ts < end and e.ts + e.dur > start)
    share = sum(b - a for a, b in covered) / (end - start)
    assert share >= 0.95


def test_every_span_is_a_host_event_on_the_profilers_clock(traced):
    tracer, host = traced[0], traced[4]
    spans = [e for e in tracer.events if e.domain == "wall_s"]
    offsets = []
    for name in {e.name for e in spans}:
        ours = sorted(e.ts for e in spans if e.name == name)
        theirs = sorted(h.start_ns for h in host if h.name == name)
        assert len(theirs) == len(ours), name
        offsets += [h * 1e-9 - t for h, t in zip(theirs, ours)]
    assert max(offsets) - min(offsets) <= 0.2e-3


def test_untraced_run_records_no_span(tmp_path):
    import jax
    _profile(tmp_path)
    try:
        out, _, _ = _serve(None, requests=4)
    finally:
        jax.profiler.stop_trace()
    assert out["metrics"].completed == 4 and NULL.events == []
    names = {h.name for h in _xplane_events(tmp_path)}
    assert not names & set(DECODE + PREFILL + ("setup.warmup",))


def test_chrome_export_starts_wall_time_at_zero(traced):
    doc = to_chrome(traced[0])
    pids = {e["args"]["name"]: e["pid"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"}
    wall = [e for e in doc["traceEvents"] if e["ph"] == "X"
            and e["pid"] in (pids[f"wall:{LANE}"], pids["wall:engine"])]
    assert min(e["ts"] for e in wall) == 0.0
    first = min((e for e in wall if e["name"] == "prefill.admit"),
                key=lambda e: e["ts"])
    last_warmup = max((e for e in wall if e["name"] == "setup.warmup"),
                      key=lambda e: e["ts"])
    assert last_warmup["ts"] + last_warmup["dur"] <= first["ts"]


def test_traced_small_run_reports_the_span_metrics():
    res = small_run(trace=True, requests=8, seconds=1.0)
    for name in ("decode_host_ms", "token_pull_ms", "warmup_s"):
        assert res["metrics"][name]["value"] > 0, name
