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
import contract
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


def test_every_named_file_resolves():
    contract.check_named_files(ROOT)


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


class _Served:
    """A finished request as the reference reads it."""

    def __init__(self, tokens, generated):
        self.tokens, self.generated = tokens, generated
        self.prompt_len, self.gen_len = len(tokens), len(generated)


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
    from repro.models import ModelConfig, forward, init_params

    cfg = ModelConfig(name="t", family="dense", num_layers=3, d_model=64,
                      d_ff=96, vocab_size=100, vocab_pad_to=32, num_heads=4,
                      num_kv_heads=kv, head_dim=16, rope_variant=rope,
                      dtype="bfloat16")
    dense = work.description(work.load_config("chatglm3-6b"))
    m = dense.Dims(layers=3, d=64, ff=96, heads=4, kv_heads=kv, head_dim=16,
                   vocab=100, vocab_padded=128, eps=1e-5, rope_variant=rope,
                   rope_theta=10_000.0, dtype="bfloat16", chips=1)
    assert dense.program_mismatch(cfg, m) == {}
    p = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    w = dense.draw_weights(m)
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
    got = dense.forward_logits(m, toks, read)["f32"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    # The program's own first choice after each whole row, served as one
    # token, lies on the reference's best.
    first = np.asarray(want)[:, -1].argmax(-1)
    reqs = [_Served(toks[i], [first[i]]) for i in range(2)]
    gaps = dense.widest_gaps(m, reqs, control=True)
    assert gaps["served"] <= 1e-5 and gaps["tokens"] == 2


class _Random:
    """A finished request whose prompt and served tokens are random."""

    def __init__(self, rng, prompt_len, gen_len):
        self.prompt_len, self.gen_len = prompt_len, gen_len
        self.tokens = rng.integers(0, 100, prompt_len).astype(np.int32)
        self.generated = rng.integers(0, 100, gen_len).astype(np.int32)


def test_reference_draws_and_gaps_are_those_before_the_move():
    """The weights' hash and the gaps, computed by reference.py before the
    dense reference moved into models/dense_gqa.py: the move may change
    neither a draw nor a gap."""
    import hashlib

    import jax
    dense = work.description(work.load_config("chatglm3-6b"))
    m = dense.Dims(layers=3, d=64, ff=96, heads=4, kv_heads=2, head_dim=16,
                   vocab=100, vocab_padded=128, eps=1e-5, rope_variant="half",
                   rope_theta=10_000.0, dtype="bfloat16", chips=1)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(dense.draw_weights(m)):
        h.update(np.asarray(leaf.astype("float32")).tobytes())
    assert h.hexdigest() == ("93659ad1b1bea01fc074c9bea18d5fda"
                             "5beb4e9156b3a151a6343b7f37264275")
    rng = np.random.default_rng(7)
    reqs = [_Random(rng, 12, 5), _Random(rng, 7, 9), _Random(rng, 20, 3)]
    gaps = dense.widest_gaps(m, reqs, control=True, shape=(4, 32, 10))
    assert gaps["tokens"] == 17
    assert gaps["served"] == pytest.approx(5.176538944244385, rel=1e-6)
    assert gaps["control"] == pytest.approx(0.08494114875793457, rel=1e-6)


def test_a_configuration_added_as_files_only_resolves_and_runs(tmp_path):
    """In a copy of the benchmark, a configuration is added as new files
    only (its file, its model description, its cell) and as new entries of
    BENCHMARK.json: every named file resolves, and a run of the new cell
    at the program's scaled-down size is correct.  No file of the copy
    changes but BENCHMARK.json."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}

    name, cell = "toy-dense", "toy-dense.chat-decode"
    (chip / "models" / "toy_dense.py").write_text(
        (CHIP / "models" / "dense_gqa.py").read_text())
    cfg = dict(work.load_config("granite-3-8b-tp4"), name=name,
               description="toy_dense", chips=1, mesh_shape=[1, 1])
    (chip / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (chip / "cells" / f"{cell}.json").write_text(json.dumps(
        {"requests_per_s": 1.0,
         "check": {"rows": 8, "min_tokens": 300, "limit": 0.2},
         "trace": {"open_after_s": 15.0, "seconds": 4.0}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": name, "source": "https://example.org/toy", "reduced":
        cfg["reduced"], "file": f"benchmarks/chip/configs/{name}.json",
        "why": "a configuration brought as files only"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "chat-decode", "chips": 1,
                               "why": "files only"})
    for m in bench["per_layer"]:
        if m["name"] in ("host_share", "mfu"):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(chip / 'tests')!r})\n"
        "from pathlib import Path\n"
        "import contract, work\n"
        f"contract.check_named_files(Path({str(tmp_path)!r}))\n"
        f"assert work.description(work.load_config({name!r})).__file__ \\\n"
        f"    == {str(chip / 'models' / 'toy_dense.py')!r}\n"
        "from smallrun import small_run\n"
        f"res = small_run({cell!r})\n"
        "print(json.dumps({'correct': res['correct'],"
        " 'checks': res['checks']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out
    assert out["checks"]["tokens_checked"]["value"] > 0
    after = {p: p.read_bytes() for p in before if p.is_file()}
    assert after == before
