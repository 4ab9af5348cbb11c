"""The EfficientViT-L2 configuration and its cell: the file gives the
program's L2 config, the reference counts L2's published work, the
FusedMBConv readers read a hand-made device trace and nothing without
one, and a tiny L-shaped configuration runs end to end and is correct."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_copy  # noqa: E402

sys.path.insert(0, str(bench_copy.BENCH))
from benchlib import spec, system, xtrace  # noqa: E402

bench_run = spec.load_module(bench_copy.BENCH / "run.py", "bench_run_l2")
CELL = "l2-r224-fp32.offline"
READERS = ("fmbconv_ms_per_batch", "fmbconv_roofline")
TINY_L = {"widths": [16, 32, 32, 64, 64], "depths": [1, 1, 1, 1, 1],
          "head_widths": [64, 64], "num_classes": 10}


def _config():
    return json.loads((bench_copy.BENCH / "configs"
                       / "l2-r224-fp32.json").read_text())


def test_the_file_gives_the_l2_config():
    from repro.core.efficientvit import EfficientViTConfig
    assert system.model_config(_config()) == EfficientViTConfig(
        name="l2-r224-fp32", widths=(32, 64, 128, 256, 512),
        depths=(1, 2, 2, 8, 8), head_dim=32, msa_scales=(5,),
        head_widths=(3072, 3200), num_classes=1000, image_size=224,
        stage_blocks=("res", "fmb", "fmb", "mb", "att"),
        expand_ratios=(1, 4, 4, 4, 6), down_expand=4, act="gelu_tanh",
        head_norm="ln")


def test_l2_work_is_the_published_count():
    cell = spec.load_cell(CELL)
    ref = cell.reference
    assert ref.macs_per_image(cell.config) == pytest.approx(6.96e9, rel=0.01)
    flops, nbytes = ref.fmbconv_work(cell.config, 8)
    # FusedMBConv is ~46% of the MACs (S1/S2: two downsampling blocks and
    # four residual blocks)
    assert flops / (2 * 8 * ref.macs_per_image(cell.config)) == \
        pytest.approx(0.456, abs=0.005)
    assert 0 < nbytes < flops


def _run(by_op, executions=4, buckets=(8, 8, 8, 8)):
    """A traced run of the L2 cell over [1, 2) whose device trace holds
    ``by_op`` and whose host dispatched ``buckets``."""
    rec = bench_run.RunRecord(cell=spec.load_cell(CELL), t0=1.0, t1=2.0,
                              peak=197e12)
    rec.spans = [("dispatch", 1.1 + 0.1 * i, 1.101 + 0.1 * i, {"bucket": b},
                  i, None) for i, b in enumerate(buckets)]
    if by_op is not None:
        rec.device = xtrace.Reduction(
            window_s=1.0, busy_s=0.5, custom_s=sum(by_op.values()),
            xla_s=0.0, executions=executions, by_op=by_op, gaps=[])
    return rec


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_the_kernel(name):
    read = spec.metric_reader(name)
    assert read(_run(None)) is None
    # a program without the kernel (the parent): no fmbconv_op in the trace
    assert read(_run({"fusion.1 fusion f32[8,56,56,64]": 0.01})) is None


def test_readers_on_a_hand_made_trace():
    by_op = {"fmbconv_op.1 custom-call f32[8,56,56,64]": 0.004,
             "fmbconv_op.5 custom-call f32[8,28,28,128]": 0.002,
             "mbconv_op.2 custom-call f32[8,14,14,256]": 0.003,
             "fusion.1 fusion f32[8,56,56,64]": 0.001}
    run = _run(by_op)
    ms = spec.metric_reader("fmbconv_ms_per_batch")(run)
    assert ms == pytest.approx(0.006 * 1e3 / 4)
    flops, nbytes = run.cell.reference.fmbconv_work(run.cell.config, 8)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / (0.006 / 4)
    got = spec.metric_reader("fmbconv_roofline")(run)
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_a_tiny_l_configuration_runs_end_to_end_and_is_correct(tmp_path):
    root = bench_copy.make(tmp_path)
    cfg = _config()
    cfg["model"].update(TINY_L)
    cfg.update(name="l-tiny", image_size=32)
    cell = bench_copy.add_cell(
        root, cfg, "closed8",
        {"loop": "closed", "outstanding": 8, "buckets": [8],
         "deadline_ms": None, "pool": 8, "warmup_s": 0.5})
    rc, res, err = bench_copy.run_cell(root, cell, 2 ** 33 + 7, 1.0, 0)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["logit_err"]["value"] < 5e-6
    assert res["metrics"]["images_per_s"]["value"] > 0
