"""The readers of the scheduler's host-loop spans (``h2d``, ``launch``,
``readback``, ``admit``) on hand-made runs, and the int8 offline cell
found by name with every per-layer metric it reports."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

bench_run = spec.load_module(BENCH / "run.py", "bench_run_host_spans")

NEW = ("h2d_ms_per_batch", "readback_ms_per_batch",
       "pipeline_empty_ms_per_batch", "admit_wait_p99_ms")


def _run(spans, t0=1.0, t1=2.0):
    """A traced run over [t0, t1) holding ``spans``: (name, start, end)
    or (name, start, end, attrs)."""
    rec = bench_run.RunRecord(t0=t0, t1=t1)
    rec.spans = [(s[0], s[1], s[2], s[3] if len(s) > 3 else {}, i, None)
                 for i, s in enumerate(spans)]
    return rec


def _batch(disp, launch_end, read_start, read_end, error=None):
    """One batch: dispatch at ``disp`` (1 ms), its copy and launch inside
    it, in flight from ``launch_end`` to the end of its readback."""
    rb = {"error": error} if error else {}
    return [("dispatch", disp, disp + 0.001),
            ("h2d", disp + 0.0002, disp + 0.0005),
            ("launch", disp + 0.0005, launch_end),
            ("readback", read_start, read_end, rb)]


def read(name, run):
    return spec.metric_reader(name)(run)


def test_per_batch_means_count_spans_opening_in_the_window():
    spans = (_batch(0.9995, 1.0009, 1.001, 1.004)        # dispatch before t0
             + _batch(1.100, 1.1009, 1.101, 1.105)
             + _batch(1.500, 1.5009, 1.501, 1.503)
             + _batch(1.9995, 2.0009, 2.001, 2.004))     # readback after t1
    run = _run(spans)
    # three batches dispatched inside; the first batch's copy opens
    # before the window and the last one's readback after it
    assert read("h2d_ms_per_batch", run) == pytest.approx(
        (0.0003 + 0.0003 + 0.0003) * 1e3 / 3)
    assert read("readback_ms_per_batch", run) == pytest.approx(
        (0.003 + 0.004 + 0.002) * 1e3 / 3)


def test_pipeline_empty_clips_straddling_batches_to_the_window():
    # in flight: [0.95, 1.10) straddles t0, [1.30, 1.40), [1.90, 2.20)
    # straddles t1; empty in [1, 2): [1.10, 1.30) + [1.40, 1.90)
    spans = (_batch(0.949, 0.95, 0.96, 1.10)
             + _batch(1.299, 1.30, 1.31, 1.40)
             + _batch(1.899, 1.90, 1.91, 2.20))
    assert read("pipeline_empty_ms_per_batch", _run(spans)) == \
        pytest.approx(0.70 * 1e3 / 2)


def test_pipeline_empty_with_two_overlapping_batches():
    # A in flight [1.10, 1.50), B [1.20, 1.60): the pipeline is empty on
    # [1.00, 1.10) and [1.60, 2.00) only, not in the overlap
    spans = (_batch(1.099, 1.10, 1.11, 1.50)
             + _batch(1.199, 1.20, 1.51, 1.60))
    assert read("pipeline_empty_ms_per_batch", _run(spans)) == \
        pytest.approx(0.50 * 1e3 / 2)


def test_pipeline_empty_counts_an_errored_readback_as_an_end():
    # the failed read takes the batch out of flight; its retry, whose
    # first launch raised, enqueues nothing until the second launch
    spans = (_batch(1.099, 1.10, 1.11, 1.30, error="ExecutorError")
             + [("dispatch", 1.50, 1.501),
                ("launch", 1.5005, 1.5008, {"error": "ExecutorError"}),
                ("dispatch", 1.70, 1.701)]
             + _batch(1.70, 1.75, 1.76, 1.80)[1:])
    assert read("pipeline_empty_ms_per_batch", _run(spans)) == \
        pytest.approx((0.10 + 0.45 + 0.20) * 1e3 / 3)


def test_admit_wait_p99_is_the_nearest_rank():
    waits = [0.0001 * (i + 1) for i in range(200)]       # 0.1 .. 20 ms
    spans = [("admit", 1.0 + i * 1e-3, 1.0 + i * 1e-3 + w)
             for i, w in enumerate(waits)]
    spans.append(("admit", 0.5, 0.6))                    # before the window
    # nearest rank: the 198th of 200 sorted waits
    assert read("admit_wait_p99_ms", _run(spans)) == pytest.approx(19.8)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_without_the_new_spans(name):
    """A program that records only the older spans (dispatch, device,
    finalize, request) gives no reading and raises nothing."""
    spans = [("dispatch", 1.1, 1.101), ("device", 1.101, 1.105),
             ("finalize", 1.105, 1.106), ("request", 1.0, 1.106)]
    assert read(name, _run(spans)) is None


def test_int8_offline_cell_is_found_with_all_ten_metrics():
    cell = spec.load_cell("b1-r224-int8.offline")
    assert cell.chips == 1
    assert cell.config["name"] == "b1-r224-int8"
    assert cell.config["precision"] == "int8"
    assert cell.traffic["loop"] == "closed"
    assert cell.traffic["buckets"] == [8]
    assert [m["name"] for m in cell.end_to_end] == ["images_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 10 and set(NEW) <= set(names)
    assert "launch_empty_share" in names
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
