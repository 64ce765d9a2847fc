"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process loads the cell's model through the program's entry point
``repro.serve.serve_workload`` (``fabric="wallclock"``, every other option
at its default), serves the cell's job and prints one JSON line.  The job
is a backlog of ``ceil(requests_per_s * seconds)`` requests, submitted in
the order the traffic file fixes or the seed draws (``traffic.py``);
``requests_per_s`` (``cells/<cell>.json``) is set so the backlog lasts
about ``seconds``.  The window runs on the host clock from the first
admission to the first token of the last request taken from the backlog:
the time in which the queue was never empty.  The drain after it, with
ever fewer slots busy, depends on the submission order more than on the
program, and is not measured.
Set-up (imports, backend, weights, warm-up, compile-cache loads) runs from
process start to the first admission; its phases are printed on stderr.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``cells/<cell>.json``
and ``metrics/<metric>.py``; the configuration names its model description,
``models/<description>.py`` (``work.description``), through which alone
the harness reaches the model's sizes, work counts and reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` attaches
the program's tracer, takes a profiler trace of a few seconds inside the
job and reports the per-layer metrics.  Either way the served tokens of a
sample of requests are checked against the description's float32
reference once the window has closed.

The run exits 2 and prints no result where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()
#: Host-clock stamps of set-up's phases, in order.
PHASES: list[tuple[str, float]] = [("start", T_START)]

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import traffic  # noqa: E402
import work  # noqa: E402

PHASES.append(("harness imported", time.perf_counter()))

#: Where the persistent compilation cache goes unless the environment
#: names one: a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"

#: Host buffer the TPU runtime maps for transfers at start-up, unless the
#: environment names one.  Its default takes 5 to 14 s to map on a host
#: without transparent huge pages, and the time varies from run to run;
#: this size maps in about 1 s and holds every transfer a run makes (the
#: prompts and the tokens of a step).
PREMAPPED_BUFFER_BYTES = 256 << 20

#: Program names of the two step kinds in the device trace.
DECODE_PROGRAM = "decode_fn"
PREFILL_PROGRAM = "slot_prefill_step"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def load_cell(name: str) -> dict:
    return json.loads((HERE / "cells" / f"{name}.json").read_text())


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_names(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


# --------------------------------------------------------------------------- #
# Profiler window, opened and closed between two steps
# --------------------------------------------------------------------------- #
class TraceWindow:
    """Opens the profiler at the first step boundary ``open_after_s`` into
    the job, and closes it at the first one ``seconds`` later that follows
    a whole prefill inside the window (or when the job ends), so that both
    step kinds are in every trace.

    A step boundary is a request stamp: the loop sets ``t_first_token``
    after a prefill and ``t_done`` after a decode step, each once the step
    has finished on the device and before the next is dispatched, so every
    program run in the trace is whole.  A prefill's members share one
    virtual ``t_first_token``; the prefills inside the window are those
    whose first member was stamped after the opening and no later than the
    closing.
    """

    def __init__(self, open_after_s: float, seconds: float, log_dir: str):
        self.open_after_s, self.seconds = open_after_s, seconds
        self.log_dir = log_dir
        self.t0 = None               # first admission
        self.t_open = self.t_close = None
        self.open_group = None
        self.prefill_inside = False  # a prefill began and ended inside
        self.overhead_s = 0.0        # profiler start and stop inside the job

    def on_stamp(self, req, field, t):
        if field == "t_admitted":
            if self.t0 is None:
                self.t0 = t
            return
        if self.t_close is not None:
            return
        if self.t_open is None:
            if t - self.t0 >= self.open_after_s:
                self._start(req.t_first_token
                            if field == "t_first_token" else None)
        else:
            if field == "t_first_token" \
                    and req.t_first_token == self.open_group:
                return
            self.prefill_inside |= field == "t_first_token"
            if self.prefill_inside and t - self.t_open >= self.seconds:
                self.close(in_job=True)

    def _start(self, group):
        import jax
        t = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t_open = time.perf_counter()
        self.overhead_s += self.t_open - t
        self.open_group = group

    def close(self, in_job: bool = False):
        """Stop the profiler (at the end of the job if no step came)."""
        if self.t_open is None or self.t_close is not None:
            return
        import jax
        self.t_close = time.perf_counter()
        jax.profiler.stop_trace()
        if in_job:
            self.overhead_s += time.perf_counter() - self.t_close

    def prefills(self, reqs) -> list[tuple[int, int]]:
        """(rows, prompt_len) of each prefill completed inside the window."""
        groups: dict[float, list] = {}
        for r in reqs:
            if "t_first_token" in r.__dict__.get("wall", {}):
                groups.setdefault(r.t_first_token, []).append(r)
        out = []
        for g, members in groups.items():
            first = min(r.wall["t_first_token"] for r in members)
            if g != self.open_group and self.t_open < first <= self.t_close:
                out.append((len(members), members[0].prompt_len))
        return out


def reduce_trace(win: TraceWindow, reqs) -> tuple[dict, dict]:
    """Device-trace quantities of the window, and the ``breakdown``."""
    import reduce
    files = sorted(Path(win.log_dir).rglob("*.xplane.pb"))
    if not files:
        return {}, {}
    events = reduce.load(str(files[-1]))
    devs = reduce.devices(events)
    window_s = win.t_close - win.t_open
    if not devs:
        return {"window_s": window_s, "busy_s": None,
                "decode_runs": 0, "prefill_runs": 0, "prefills": []}, {}
    d0 = devs[0]
    dec_ns, dec_runs = reduce.program_time(events, d0, DECODE_PROGRAM)
    pre_ns, pre_runs = reduce.program_time(events, d0, PREFILL_PROGRAM)
    tr = {
        "window_s": window_s,
        "busy_s": statistics.fmean(reduce.busy_ns(events, d) for d in devs)
        * 1e-9,
        "decode_s": dec_ns * 1e-9, "decode_runs": dec_runs,
        "prefill_s": pre_ns * 1e-9, "prefill_runs": pre_runs,
        "prefills": win.prefills(reqs),
        "devices": len(devs),
        "collective_s": [reduce.collective_ns(events, d) * 1e-9
                         for d in devs],
    }
    breakdown = {"device_ops": reduce.top_ops(events, d0),
                 "idle_gaps": reduce.idle_gaps(events, d0)}
    return tr, breakdown


# --------------------------------------------------------------------------- #
# The check of served tokens
# --------------------------------------------------------------------------- #
def sample_for_check(done, rows: int, seed: int):
    """The longest finished request and ``rows - 1`` others drawn from the
    seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.gen_len, r.prompt_len, -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC4EC])
    pick = rng.choice(len(rest), size=min(rows - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check_served(model, m, reqs, shape: tuple, check: dict, seed: int,
                 control: bool = False) -> tuple[int, dict, dict | None]:
    """(failed requests, {number: {value, limit}}, the control's numbers)
    of the served job, judged by the description ``model``'s reference at
    sizes ``m``; ``shape`` pads the reference's rows to one size.

    ``control`` also puts the fp8 control in the program's place on the
    same rows: its own first choices are scored as its ``logit_gap``, beside
    the same ``failed_requests`` and ``tokens_checked``, for ``judge``."""
    from repro.serve import RequestState

    failed = 0
    done = []
    for r in reqs:
        gen = None if r.generated is None else np.asarray(r.generated)
        ok = (r.state is RequestState.DONE and gen is not None
              and gen.shape == (r.gen_len,)
              and bool(((gen >= 0) & (gen < m.vocab)).all()))
        failed += not ok
        if ok:
            done.append(r)
    numbers = {"failed_requests": {"value": failed, "limit": 0}}
    sample = sample_for_check(done, check["rows"], seed)
    control_numbers = None
    if sample:
        gaps = model.widest_gaps(m, sample, shape=(check["rows"],) + shape,
                                 control=control)
        numbers["logit_gap"] = {"value": gaps["served"],
                                "limit": check["limit"]}
        numbers["tokens_checked"] = {"value": gaps["tokens"],
                                     "limit": check["min_tokens"]}
        if control:
            control_numbers = dict(numbers, logit_gap={
                "value": gaps["control"], "limit": check["limit"]})
    return failed, numbers, control_numbers


def judge(numbers: dict) -> bool:
    """Correct: every request done, and the widest gap of enough checked
    tokens within its limit."""
    gap = numbers.get("logit_gap")
    tokens = numbers.get("tokens_checked")
    return (numbers["failed_requests"]["value"] == 0 and gap is not None
            and gap["value"] <= gap["limit"]
            and tokens["value"] >= tokens["limit"])


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #
def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def steady_window(reqs) -> tuple[float, float, float]:
    """(start, end, output tokens) of the window in which the backlog was
    never empty: from the first admission to the first token of the last
    request taken from the queue.

    A request finished by the end counts all its tokens.  One still
    decoding at the end counts its first token and the share of its
    decode steps that fell before the end, by its own pace (one token
    per step between its first token and its last)."""
    walls = [r.wall for r in reqs if "wall" in r.__dict__]
    start = min(w["t_admitted"] for w in walls if "t_admitted" in w)
    end = max(w["t_first_token"] for w in walls if "t_first_token" in w)
    tokens = 0.0
    for r in reqs:
        w = r.__dict__.get("wall", {})
        if "t_done" not in w:
            continue
        first, done = w["t_first_token"], w["t_done"]
        if done <= end:
            tokens += r.gen_len
        elif first <= end:
            tokens += 1 + (r.gen_len - 1) * (end - first) / (done - first)
    return start, end, tokens


def run(cell_name: str, *, seed: int, seconds: float, trace: bool,
        cfg: dict, mix: dict, cell: dict, bench: dict,
        reduced: bool = False, dims=None,
        peak: dict | None = None, control: bool = False,
        log=print) -> dict:
    """Serve one job and return the result line as a dict.

    ``reduced`` serves the program's scaled-down model (tests on the CPU);
    ``dims`` then gives its sizes to the description, and ``peak`` stands in
    for the device's peaks.  ``control`` also judges the fp8 control in
    the program's place (``result["control"]``).
    """
    import jax
    from repro.obs import Tracer
    from repro.serve import ServeConfig, serve_workload

    durations: list[tuple[float, str, float]] = []   # (when, event, s)
    cache_hits = [0]

    def on_duration(event, secs, **_):
        durations.append((time.perf_counter(), event, secs))

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    model = work.description(cfg)
    m = dims or model.dims(cfg)
    dev = device_record()
    peak = peak or work.load_peaks(dev["kind"])
    n = math.ceil(cell["requests_per_s"] * seconds)
    prompts, gens = traffic.lengths(mix, n)
    tmp = tempfile.mkdtemp(prefix="chip-trace-") if trace else None
    win = None
    if trace:
        tw = cell["trace"]
        win = TraceWindow(min(tw["open_after_s"], 0.3 * seconds),
                          min(tw["seconds"], 0.3 * seconds), tmp)
    _, StampedSpec = traffic.stamped_types(
        win.on_stamp if win else None)
    spec = StampedSpec(num_requests=n, seed=seed, mix=json.dumps(mix))
    tracer = Tracer() if trace else None
    config = ServeConfig(arch=cfg["arch"], reduced=reduced, execute=True,
                         fabric="wallclock", max_batch=cfg["max_batch"],
                         mesh_shape=tuple(cfg["mesh_shape"]), tracer=tracer)
    t_call = time.perf_counter()
    out = serve_workload(spec, config=config)
    if win is not None:
        win.close()
    mem_peak = peak_bytes()
    reqs = out["requests"]
    serve_metrics = out["metrics"]
    engine_setup_s = out["engine_setup_s"]
    del out
    gc.collect()

    t_first, t_end, out_tokens = steady_window(reqs)
    window_s = t_end - t_first
    job_s = max(r.wall["t_done"] for r in reqs
                if "t_done" in r.__dict__.get("wall", {})) - t_first
    setup_s = t_first - T_START
    compiles = [(t, s) for t, e, s in durations
                if e == "/jax/core/compile/backend_compile_duration"]
    in_window = sum(t_first <= t <= t_end for t, _ in compiles)
    in_setup: dict[str, float] = {}
    for t, e, s in durations:
        if t < t_first:
            in_setup[e] = in_setup.get(e, 0.0) + s
    stamps = PHASES + [("serve_workload called", t_call),
                       ("first admission", t_first)]
    phases = {f"{a} -> {b}": round(tb - ta, 4)
              for (a, ta), (b, tb) in zip(stamps, stamps[1:])}
    log(f"job: {n} requests, window {window_s} s of {job_s} s served, "
        f"set-up {setup_s} s ({len(compiles)} compiles, "
        f"{cache_hits[0]} compile-cache hits), {in_window} compiles inside "
        f"the window; live arrays after the window "
        f"{sum(a.nbytes for a in jax.live_arrays())} bytes")
    log(f"set-up phases (s): {json.dumps(phases)}; of the last, the engine's "
        f"weights and warm-up {engine_setup_s}; jax.monitoring durations "
        f"before the window (s): "
        f"{json.dumps({k: round(v, 4) for k, v in in_setup.items()})}")

    shape = (int(prompts.max() + gens.max() - 1), int(gens.max()))
    failed, numbers, control_numbers = check_served(
        model, m, reqs, shape, cell["check"], seed, control)
    correct = judge(numbers)

    metrics = {}
    names = metric_names(bench, cell_name, trace)
    if not trace:
        done = [r for r in reqs if "t_done" in r.__dict__.get("wall", {})]
        tpot = [(r.wall["t_done"] - r.wall["t_first_token"])
                / (r.gen_len - 1) for r in done if r.gen_len > 1]
        values = {"output_tok_per_s": out_tokens / window_s,
                  "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95))
                  if tpot else None,
                  "setup_s": setup_s}
    else:
        tr, breakdown = reduce_trace(win, reqs)
        shutil.rmtree(tmp, ignore_errors=True)
        spans: dict[str, list[float]] = {}
        for e in tracer.events:
            if e.domain == "wall_s" and e.ph == "X":
                spans.setdefault(e.name, []).append(e.dur)
        lengths = [(r.prompt_len, r.gen_len) for r in reqs]
        rec = {
            "job_s": job_s - win.overhead_s,
            "serve_metrics": serve_metrics, "engine_spans": spans,
            "trace": tr, "dims": m, "model": model, "peak": peak,
            "lengths": lengths,
            "max_batch": cfg["max_batch"],
            "decode_steps": len(spans.get("decode", [])),
            "decode_positions": work.decode_live_positions(
                *zip(*lengths)) if lengths else 0,
            "decode_flops": sum(
                model.request_flops(m, p, g) - model.prefill_flops(m, p)
                for p, g in lengths),
            "memory_peak_bytes": mem_peak,
        }
        values = {mm["name"]: metric_reader(mm["name"])(rec) for mm in names}
        log(f"trace: {json.dumps({k: v for k, v in tr.items() if k != 'prefills'})}; "
            f"{len(tr.get('prefills', []))} prefills inside; profiler "
            f"overhead {win.overhead_s} s")
    for mm in names:
        v = values.get(mm["name"])
        if v is not None:
            metrics[mm["name"]] = {"value": v, "unit": mm["unit"]}

    device = dict(dev, memory_peak_bytes=mem_peak)
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        if tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr.get("window_s")
        result["breakdown"] = breakdown
    if control_numbers is not None:
        result["control"] = {"correct": judge(control_numbers),
                             "checks": control_numbers}
    result["checks"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    entry = find_cell(bench, args.workload)
    cfg = work.load_config(entry["config"])
    mix = traffic.load_mix(entry["traffic"])
    cell = load_cell(args.workload)

    if not chip_ready(entry, cfg):
        return 2
    result = run(args.workload, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), cfg=cfg, mix=mix, cell=cell,
                 bench=bench,
                 log=lambda s: print(s, file=sys.stderr, flush=True))
    for name, num in result["checks"].items():
        print(f"check {name}: {num['value']} (limit {num['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def chip_ready(entry: dict, cfg: dict) -> bool:
    """Set up JAX for a run on the chips; False (with a reason on stderr)
    where there is no TPU, too few chips, or the program's model differs
    from the configuration file."""
    PHASES.append(("arguments read", time.perf_counter()))
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return False
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE",
                          str(PREMAPPED_BUFFER_BYTES))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    PHASES.append(("jax imported", time.perf_counter()))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    backend = jax.default_backend()
    PHASES.append(("backend up", time.perf_counter()))
    if backend != "tpu":
        print(f"run.py: JAX backend is {backend!r}, not 'tpu'; nothing is "
              f"measured off the chip", file=sys.stderr)
        return False
    if len(jax.devices()) < entry["chips"]:
        print(f"run.py: {entry['chips']} chips asked, {len(jax.devices())} "
              f"found", file=sys.stderr)
        return False
    from repro.configs import get_config
    model = work.description(cfg)
    mismatch = model.program_mismatch(get_config(cfg["arch"]),
                                      model.dims(cfg))
    if mismatch:
        print(f"run.py: the program's {cfg['arch']} differs from "
              f"configs/{entry['config']}.json: {mismatch}", file=sys.stderr)
        return False
    PHASES.append(("program imported", time.perf_counter()))
    return True


if __name__ == "__main__":
    sys.exit(main())
