"""Checks that every file ``BENCHMARK.json`` names resolves, shared by the
test of this tree and the test of a copy that a new configuration was
added to as files only."""

import json
from pathlib import Path

import chip_paths  # noqa: F401  (before the modules below)
import run
import traffic
import work


def check_named_files(root: Path) -> None:
    """Raise AssertionError where a configuration, its model description,
    a traffic mix, a cell or a metric reader named by ``root``'s
    ``BENCHMARK.json`` does not resolve."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        m = work.description(cfg).dims(cfg)
        hash(m)    # a static argument of the reference's jitted programs
        assert m.chips == cfg["chips"] and m.vocab > 0 and m.dtype
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
        assert m["workloads"], m["name"]
        for w in m["workloads"]:
            assert w in cells, (m["name"], w)
    for m in bench["end_to_end"]:
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)
