"""The harness's contract, checked on the CPU: BENCHMARK.json and the
files it names, the result line, the refusal without a chip, the stamped
requests, the traffic generator and the reference's weights."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chip_paths  # noqa: F401  (before the modules below)
import run
import traffic
import work
from smallrun import MIX, small_run

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_named_file_resolves(bench):
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        work.dims(cfg)
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic.load_mix(w["traffic"])
        cell = run.load_cell(w["name"])
        assert cell["requests_per_s"] > 0 and cell["check"]["limit"] > 0
        assert work.load_config(w["config"])["chips"] == w["chips"]
        assert len(run.metric_names(bench, w["name"], True)) >= 1
        assert len(run.metric_names(bench, w["name"], False)) >= 2
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "chatglm3-6b.chat-decode", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu_and_prints_no_result():
    res = _cli(ROOT)
    assert res.returncode == 2
    assert "not 'tpu'" in res.stderr
    assert "{" not in res.stdout


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode == 2
    assert "src/repro) is not in" in res.stderr
    assert "{" not in res.stdout


def test_result_line_keys_and_a_correct_run():
    res = small_run()
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 6
    assert set(res["metrics"]) == {"output_tok_per_s", "tpot_p95_ms",
                                   "setup_s"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    checks = res["checks"]
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    # Every request is checked, each with all its output tokens.
    assert checks["tokens_checked"]["value"] == \
        int(traffic.lengths(MIX, 6)[1].sum())


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    res = small_run(trace=True, requests=8, seconds=1.0)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    # The host-side layers read something on any backend; the device
    # layers only from a TPU trace.
    for name in ("host_share", "decode_step_ms", "prefill_ms_per_request",
                 "mfu"):
        assert name in res["metrics"]
    assert "collective_share" not in res["metrics"]
    assert "window_s" in res["device"]


def test_stamped_requests_survive_serve_workload():
    from repro.serve import RequestState, ServeConfig, serve_workload
    stamps = []
    _, Spec = traffic.stamped_types(lambda r, f, t: stamps.append(f))
    spec = Spec(num_requests=6, seed=2**33 + 5, mix=json.dumps(MIX))
    out = serve_workload(spec, config=ServeConfig(
        arch="chatglm3-6b", reduced=True, execute=True, fabric="wallclock",
        max_batch=4))
    reqs = out["requests"]
    assert len(reqs) == 6
    for r in reqs:
        assert r.state is RequestState.DONE
        w = r.wall
        assert w["t_admitted"] <= w["t_first_token"] <= w["t_done"]
        assert np.asarray(r.generated).shape == (r.gen_len,)
    assert stamps.count("t_done") == 6


def test_lengths_are_the_quantiles_of_the_mix():
    mix = traffic.load_mix("chat-decode")
    prompts, gens = traffic.lengths(mix, 90)
    # Prompts on the 32-token grid, cut to [16, 512]; outputs cut to
    # [8, 768]; the log-normal's median in the middle of each.
    assert set(prompts % 32) == {0} and prompts.min() >= 32 \
        and prompts.max() <= 512
    assert gens.min() >= 8 and gens.max() == 768
    assert list(gens) == sorted(gens)
    assert np.median(gens) == pytest.approx(mix["gen_len"]["median"], abs=6)
    # LMSYS-Chat-1M's means, within what the cuts and the grid move.
    assert prompts.mean() == pytest.approx(69.5, rel=0.1)
    assert gens.mean() == pytest.approx(214.5, rel=0.05)
    # The pairing is fixed by the file, and another pairing seed pairs
    # otherwise.
    assert np.array_equal(prompts, traffic.lengths(mix, 90)[0])
    other = traffic.lengths(dict(mix, pairing_seed=2), 90)[0]
    assert not np.array_equal(prompts, other)
    assert sorted(other) == sorted(prompts)


@pytest.mark.parametrize("mix_name,own_order", [("chat-decode", True),
                                                ("code-completion", False)])
def test_every_seed_gets_the_same_requests(mix_name, own_order):
    mix = traffic.load_mix(mix_name)
    a = traffic.build_requests(mix, 90, 1, 1000, dict)
    b = traffic.build_requests(mix, 90, 2**40 + 3, 1000, dict)
    pairs = sorted(zip(*(x.tolist() for x in traffic.lengths(mix, 90))))
    assert sorted((r["prompt_len"], r["gen_len"]) for r in a) == pairs
    assert sorted((r["prompt_len"], r["gen_len"]) for r in b) == pairs
    # Each seed submits in its own order, unless the file fixes one.
    same = [(r["prompt_len"], r["gen_len"]) for r in a] == \
        [(r["prompt_len"], r["gen_len"]) for r in b]
    assert same is not own_order
    assert [r["rid"] for r in b] == list(range(90))
    assert all(r["arrival"] == 0.0 and r["slo_cycles"] is None for r in b)
    assert all(r["tokens"].shape == (r["prompt_len"],) for r in b)
    assert not np.array_equal(a[0]["tokens"], b[0]["tokens"])
    again = traffic.build_requests(mix, 90, 2**40 + 3, 1000, dict)
    assert all(np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(b, again))


class _Req:
    def __init__(self, gen_len, **wall):
        self.gen_len, self.wall = gen_len, wall


def test_the_window_ends_when_the_backlog_does():
    reqs = [_Req(5, t_admitted=1.0, t_first_token=2.0, t_done=4.0),
            # taken last: its first token ends the window
            _Req(3, t_admitted=1.0, t_first_token=6.0, t_done=7.0),
            # half its decode steps fall inside
            _Req(9, t_admitted=1.0, t_first_token=4.0, t_done=8.0),
            # finished exactly at the end
            _Req(2, t_admitted=1.0, t_first_token=5.0, t_done=6.0)]
    start, end, tokens = run.steady_window(reqs)
    assert (start, end) == (1.0, 6.0)
    assert tokens == 5 + 1 + (1 + 8 * 0.5) + 2


@pytest.mark.parametrize("rope,kv", [("half", 2), ("full", 4)])
def test_reference_draws_the_programs_weights_and_logits(rope, kv):
    import jax
    import jax.numpy as jnp
    import reference
    from repro.models import ModelConfig, forward, init_params

    cfg = ModelConfig(name="t", family="dense", num_layers=3, d_model=64,
                      d_ff=96, vocab_size=100, vocab_pad_to=32, num_heads=4,
                      num_kv_heads=kv, head_dim=16, rope_variant=rope,
                      dtype="bfloat16")
    m = work.Dims(layers=3, d=64, ff=96, heads=4, kv_heads=kv, head_dim=16,
                  vocab=100, vocab_padded=128, eps=1e-5, rope_variant=rope,
                  rope_theta=10_000.0, dtype="bfloat16", chips=1)
    p = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    w = reference.draw_weights(m)
    assert bool((p["embed"] == w["embed"]).all())
    assert bool((p["lm_head"][:, :100] == w["head"]).all())
    for i in range(3):
        for name in ("wq", "wk", "wv", "wo"):
            assert bool((p["groups"][0]["attn"][name][i]
                         == w["layers"][i][name]).all())
        for name in ("w_in", "w_out", "w_gate"):
            assert bool((p["groups"][0]["mlp"][name][i]
                         == w["layers"][i][name]).all())
    import dataclasses
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    toks = np.random.default_rng(0).integers(0, 100, (2, 12)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want = forward(p32, cfg32, tokens=jnp.asarray(toks))[..., :100]
    read = np.tile(np.arange(12), (2, 1)).astype(np.int32)
    got = reference.forward_logits(m, toks, read)["f32"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
