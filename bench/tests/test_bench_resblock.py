"""The reader of the ResBlock stem kernel's device time
(``resblock_ms_per_batch``) on hand-made traced runs of the L2 cell:
nothing where the trace holds no ``resblock_op``, the kernel's
milliseconds per execution where it does."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec, xtrace  # noqa: E402

bench_run = spec.load_module(BENCH / "run.py", "bench_run_resblock")
CELL = "l2-r224-fp32.offline"
read = spec.metric_reader("resblock_ms_per_batch")


def _run(by_op, executions=4, buckets=(8, 8, 8, 8)):
    """A traced run of the L2 cell over [1, 2) whose device trace holds
    ``by_op`` (none at all for None) and whose host dispatched
    ``buckets``."""
    rec = bench_run.RunRecord(cell=spec.load_cell(CELL), t0=1.0, t1=2.0,
                              peak=197e12)
    rec.spans = [("dispatch", 1.1 + 0.1 * i, 1.101 + 0.1 * i, {"bucket": b},
                  i, None) for i, b in enumerate(buckets)]
    if by_op is not None:
        rec.device = xtrace.Reduction(
            window_s=1.0, busy_s=0.5, custom_s=sum(by_op.values()),
            xla_s=0.0, executions=executions, by_op=by_op, gaps=[])
    return rec


@pytest.mark.parametrize("by_op", [
    None,
    # the parent's program: the stem's two convs are XLA fusions
    {"fusion.24 fusion f32[8,112,112,32]": 0.0015,
     "fusion.29 fusion f32[8,112,112,32]": 0.0015},
    # other kernels only
    {"fmbconv_op.1 custom-call f32[8,56,56,64]": 0.004,
     "mbconv_op.2 custom-call f32[8,14,14,256]": 0.003},
], ids=["no-trace", "xla-convs", "other-kernels"])
def test_reader_gives_nothing_without_the_kernel(by_op):
    assert read(_run(by_op)) is None


def test_reader_gives_nothing_without_executions():
    by_op = {"resblock_op.1 custom-call f32[8,112,28,128]": 0.002}
    assert read(_run(by_op, executions=0, buckets=())) is None


def test_reader_on_a_hand_made_trace():
    by_op = {"resblock_op.1 custom-call f32[8,112,28,128]": 0.002,
             "resblock_op.3 custom-call f32[8,112,28,128]": 0.0004,
             "fmbconv_op.6 custom-call f32[8,56,56,64]": 0.004,
             "fusion.24 fusion f32[8,112,112,32]": 0.001}
    assert read(_run(by_op)) == pytest.approx(0.0024 * 1e3 / 4)
