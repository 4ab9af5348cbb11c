"""The reader of the ``inflight`` attribute of the scheduler's
``launch`` spans (``launch_empty_share``) on hand-made runs."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

bench_run = spec.load_module(BENCH / "run.py", "bench_run_launch_empty")
read = spec.metric_reader("launch_empty_share")


def _run(spans, t0=1.0, t1=2.0):
    """A traced run over [t0, t1) holding ``spans``: (name, start, attrs)."""
    rec = bench_run.RunRecord(t0=t0, t1=t1)
    rec.spans = [(name, start, start + 0.0005, attrs, i, None)
                 for i, (name, start, attrs) in enumerate(spans)]
    return rec


def test_share_of_launches_into_an_empty_pipeline():
    spans = [("launch", 1.1, {"inflight": 0}),
             ("launch", 1.2, {"inflight": 1}),
             ("launch", 1.3, {"inflight": 2}),
             ("launch", 1.4, {"inflight": 3}),
             ("launch", 1.5, {"inflight": 0}),
             ("dispatch", 1.6, {}),
             ("launch", 0.9, {"inflight": 0}),     # before the window
             ("launch", 2.0, {"inflight": 0})]     # at its end
    assert read(_run(spans)) == pytest.approx(100.0 * 2 / 5)


def test_errored_launches_are_left_out():
    spans = [("launch", 1.1, {"inflight": 0, "error": "ExecutorError"}),
             ("launch", 1.2, {"inflight": 0}),
             ("launch", 1.3, {"inflight": 1}),
             ("launch", 1.4, {"inflight": 2, "error": "KernelLaunchError"}),
             ("launch", 1.5, {"inflight": 1}),
             ("launch", 1.6, {"inflight": 1})]
    assert read(_run(spans)) == pytest.approx(25.0)


@pytest.mark.parametrize("spans", [
    [("launch", 1.1, {}), ("launch", 1.2, {}), ("dispatch", 1.1, {})],
    [("dispatch", 1.1, {}), ("device", 1.2, {}), ("readback", 1.3, {})],
    [("launch", 1.1, {"inflight": 0, "error": "ExecutorError"})],
    [],
], ids=["no-attribute", "no-launch", "only-errored", "empty"])
def test_no_reading_without_the_attribute(spans):
    """A program whose launches carry no ``inflight`` gives no reading
    and raises nothing."""
    assert read(_run(spans)) is None
