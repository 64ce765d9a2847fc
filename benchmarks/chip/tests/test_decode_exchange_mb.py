"""``decode_exchange_mb`` reads the engine's count of the bytes a decode
step exchanges between chips, and nothing where the program has no such
counter or exchanges nothing."""

from types import SimpleNamespace

import pytest

import chip_paths  # noqa: F401  (before the modules below)
import run

READ = run.metric_reader("decode_exchange_mb")


@pytest.mark.parametrize("exchanged,mb", [
    (1, 1e-6), (71_959_048, 71.959048), (5 * 10**8, 500.0)])
def test_megabytes_a_decode_step_exchanges(exchanged, mb):
    rec = {"serve_metrics": SimpleNamespace(decode_exchange_bytes=exchanged)}
    assert READ(rec) == pytest.approx(mb)


@pytest.mark.parametrize("metrics", [
    SimpleNamespace(),                             # no counter in the program
    SimpleNamespace(decode_exchange_bytes=0)],     # one chip
    ids=["no_counter", "one_chip"])
def test_reads_nothing_without_a_counter_or_an_exchange(metrics):
    assert READ({"serve_metrics": metrics}) is None
