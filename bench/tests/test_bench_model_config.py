"""A configuration's ``model`` object is the program's architecture: the
harness passes all of it to ``EfficientViTConfig``, and refuses a
configuration that names a field this checkout's program lacks before
any weights are made."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_copy  # noqa: E402

sys.path.insert(0, str(bench_copy.BENCH))
from benchlib import spec, system  # noqa: E402


def _config(name):
    return json.loads((bench_copy.BENCH / "configs"
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("config", ["b1-r224-fp32", "b1-r224-int8"])
def test_b1_files_give_the_b1_config(config):
    from repro.core.efficientvit import EfficientViTConfig
    assert system.model_config(_config(config)) == EfficientViTConfig(
        name=config, widths=(16, 32, 64, 128, 256), depths=(1, 2, 3, 3, 4),
        head_dim=16, msa_scales=(5,), expand_ratio=4,
        head_widths=(1536, 1600), num_classes=1000, image_size=224)


def test_lists_arrive_as_tuples_and_the_lowering_is_cached():
    from repro.core.program import _lower, lower
    assert system.frozen([1, [2, [3, 4]], (5, [6])]) == \
        (1, (2, (3, 4)), (5, (6,)))
    a = system.model_config(bench_copy.tiny_config("b1-r224-fp32", "t"))
    b = system.model_config(bench_copy.tiny_config("b1-r224-fp32", "t"))
    assert a.widths == (8, 16, 24, 32, 48) and a.msa_scales == (5,)
    first = lower(a, batch=2)
    hits = _lower.cache_info().hits
    assert lower(b, batch=2) is first
    assert _lower.cache_info().hits == hits + 1


@pytest.mark.parametrize("bad,said", [
    ({"block_kinds": [["res"], ["fmb"]]}, "'block_kinds'"),
    ({"image_size": 64}, "'image_size'")], ids=["unknown", "repeated"])
def test_model_key_outside_the_program_is_refused_at_once(bad, said):
    cfg = bench_copy.tiny_config("b1-r224-fp32", "unservable")
    cfg["model"].update(bad)
    with pytest.raises(spec.SpecError, match=said):
        system.model_config(cfg)


def test_a_file_without_a_model_object_is_refused():
    cfg = {k: v for k, v in _config("b1-r224-fp32").items() if k != "model"}
    cfg.update(_config("b1-r224-fp32")["model"])     # the flat layout
    with pytest.raises(spec.SpecError, match="no \"model\" object"):
        system.model_config(cfg)


def test_run_refuses_an_unservable_configuration_before_weights(tmp_path):
    root = bench_copy.make(tmp_path)
    cfg = bench_copy.tiny_config("b1-r224-fp32", "unservable")
    cfg["model"]["block_kinds"] = ["res", "fmb", "fmb", "mb", "att"]
    cell = bench_copy.add_cell(
        root, cfg, "closed8",
        {"loop": "closed", "outstanding": 8, "buckets": [8],
         "deadline_ms": None, "pool": 8, "warmup_s": 0.5})
    rc, res, err = bench_copy.run_cell(root, cell, 2 ** 33 + 5, 1.0, 0)
    assert rc == 2 and res is None, err[-3000:]
    last = err.strip().splitlines()[-1]
    assert "block_kinds" in last and "unservable" in last, last
    assert "setup params and image pool" not in err
