"""The ``queue_wait_ms`` reader: the median of the responses' engine queue
wait, left out (``None``) where the program's responses do not carry it,
and read from a traced smoke run of the harness on the CPU."""
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from cellbench import spec  # noqa: E402
from test_run_cell_cpu import _run, smoke_cell  # noqa: E402,F401

READER = spec.metric_reader("queue_wait_ms")


def _run_of(responses):
    return {"requests": [{"latency_s": 1.0, "response": r}
                         for r in responses]}


def test_reads_median_queue_wait_in_ms():
    run = _run_of([{"batch": {"queue_s": q}} for q in (0.050, 0.052, 0.9)])
    assert READER.read(run) == pytest.approx(52.0)


@pytest.mark.parametrize("responses", [
    [],
    [{"batch": {"measured_rounds": 119, "measured_bytes": 1}}],
    [{"batch": {}}],
    [{"wall_s": 1.0}],
], ids=["no-requests", "parent-response", "empty-batch", "no-batch"])
def test_absent_counter_reads_none(responses):
    assert READER.read(_run_of(responses)) is None


@pytest.mark.parametrize("smoke_cell", ["hb8"], indirect=True)
def test_traced_smoke_run_reports_queue_wait(smoke_cell, capsys):
    res, _ = _run(capsys, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["queue_wait_ms"]["value"] > 0
    assert res["metrics"]["queue_wait_ms"]["unit"] == "ms"
