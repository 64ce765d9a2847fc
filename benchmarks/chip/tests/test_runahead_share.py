"""``runahead_share`` reads the loop's run-ahead counter over the job's
decode steps, and nothing from a program that has no such counter."""

from types import SimpleNamespace

import pytest

import chip_paths  # noqa: F401  (before the modules below)
import run

READ = run.metric_reader("runahead_share")


@pytest.mark.parametrize("chained,steps,share", [
    (0, 40, 0.0), (30, 40, 75.0), (40, 40, 100.0)])
def test_share_of_the_decode_steps_run_ahead(chained, steps, share):
    rec = {"serve_metrics": SimpleNamespace(decode_chained=chained),
           "decode_steps": steps}
    assert READ(rec) == pytest.approx(share)


@pytest.mark.parametrize("metrics,steps", [
    (SimpleNamespace(), 40),                      # no counter in the program
    (SimpleNamespace(decode_chained=0), 0)],      # no decode step traced
    ids=["no_counter", "no_steps"])
def test_reads_nothing_without_a_counter_or_steps(metrics, steps):
    assert READ({"serve_metrics": metrics, "decode_steps": steps}) is None
