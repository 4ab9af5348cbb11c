"""``correct`` fails where it must.  The control (the plain reference
one precision down, put in the program's place) reads above each
configuration's limit; and a run whose timed path alters an answer where
it is produced comes out not correct."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_copy  # noqa: E402

sys.path.insert(0, str(bench_copy.BENCH))
from benchlib import spec  # noqa: E402

CONFIGS = ["b1-r224-fp32", "b1-r224-int8"]


@pytest.mark.parametrize("config", CONFIGS)
def test_control_reads_above_the_limit(config):
    import control
    cfg = bench_copy.tiny_config(config, "tiny")
    ref = spec.load_module(bench_copy.BENCH / "configs" / "efficientvit.py",
                           "ref_ctl")
    for err in control.readings(cfg, ref, [3, 2 ** 32 + 9], pool=8):
        assert err > cfg["correct"]["logit_err"], (config, err)


ALTER = """
from repro.serving import executors
_call = executors.Executor.__call__
def _altered(self, params, x):
    out = _call(self, params, x)
    return out.at[0].set(-out[0])
executors.Executor.__call__ = _altered
"""


@pytest.mark.parametrize("config", CONFIGS)
def test_an_altered_answer_is_not_correct(tmp_path, config):
    root = bench_copy.make(tmp_path)
    cell = bench_copy.add_cell(
        root, bench_copy.tiny_config(config, "tiny"), "closed8",
        {"loop": "closed", "outstanding": 8, "buckets": [8],
         "deadline_ms": None, "pool": 8, "warmup_s": 0.5})
    rc, res, err = bench_copy.run_cell(root, cell, 11, 1.5, 0, patch=ALTER)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, err[-3000:]
    assert res["checks"]["logit_err"]["value"] > 1.0, err[-3000:]
    assert res["checks"]["unanswered"]["value"] == 0
