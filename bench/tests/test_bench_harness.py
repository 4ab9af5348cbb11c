"""The harness end to end on the CPU, in a scratch checkout: a new
configuration, traffic mix and per-layer metric, added as files and
entries only, are found by name and run; the result line has the
contract's keys; the run's spans and CPU trace feed the readers."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_copy  # noqa: E402

THROWAWAY_METRIC = '''
def read(run):
    """Requests of the window (a test metric)."""
    return len(run.window)
'''


def test_new_files_are_found_by_name_and_run(tmp_path):
    root = bench_copy.make(tmp_path)
    cell = bench_copy.add_cell(
        root, bench_copy.tiny_config("b1-r224-fp32", "throwaway-tiny"),
        "throwaway-open",
        {"loop": "open", "arrivals": "poisson", "rate_per_s": 40,
         "buckets": [1, 2], "deadline_ms": 2, "pool": 4, "warmup_s": 0.2},
        metrics={"throwaway_answers": THROWAWAY_METRIC})
    rc, res, err = bench_copy.run_cell(root, cell, 2 ** 33 + 1, 1.0, 1)
    assert rc == 0, err[-3000:]
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["metrics"]["throwaway_answers"]["value"] == 40
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["logit_err"]["value"] < 1e-5
    assert "check logit_err" in err.strip().splitlines()[-2]

    rc, res, err = bench_copy.run_cell(root, cell, 5, 1.0, 0)
    assert rc == 0, err[-3000:]
    m = res["metrics"]
    assert set(m) == {"images_per_s", "setup_s"}
    assert m["images_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
