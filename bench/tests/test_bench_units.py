"""Unit checks of the benchmark's yardstick: the work count, the traffic
generator and the trace reduction on hand-made operations."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec, traffic, xtrace  # noqa: E402


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config", ["b1-r224-fp32", "b1-r224-int8"])
def test_b1_macs_per_image_pinned(config):
    """EfficientViT-B1 at 224 px: 518,963,712 multiply-accumulates per
    image, the count the program's own IR gives."""
    ref = spec.load_module(BENCH / "configs" / "efficientvit.py", "ref_t")
    assert ref.macs_per_image(_config(config)) == 518_963_712


OPEN = {"loop": "open", "arrivals": "poisson", "rate_per_s": 250,
        "buckets": [4, 1, 2, 8], "deadline_ms": 3, "pool": 64,
        "warmup_s": 2}
CLOSED = {"loop": "closed", "outstanding": 32, "buckets": [8],
          "deadline_ms": None, "pool": 64, "warmup_s": 1}


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
@pytest.mark.parametrize("seed", [0, 12345, 2 ** 31 + 7, 2 ** 40 + 3])
def test_schedule_is_a_function_of_the_seed(mix, seed):
    m = traffic.Mix.parse(mix)
    a, b = traffic.schedule(m, seed, 20), traffic.schedule(m, seed, 20)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.images, b.images)
    c = traffic.schedule(m, seed + 1, 20)
    assert not np.array_equal(a.images, c.images)
    assert a.images.min() >= 0 and a.images.max() < m.pool


def test_poisson_schedule_sends_the_same_work_for_every_seed():
    m = traffic.Mix.parse(OPEN)
    for seed in (1, 2, 3):
        s = traffic.schedule(m, seed, 20)
        window = s.due_s[s.due_s >= 0]
        assert len(window) == 250 * 20
        assert len(s.due_s) - len(window) == 250 * 2
        assert np.all(np.diff(s.due_s) >= 0) and window.max() < 20
    assert m.buckets == (1, 2, 4, 8)


@pytest.mark.parametrize("bad", [
    {**OPEN, "loop": "burst"}, {**OPEN, "arrivals": "uniform"},
    {**OPEN, "rate_per_s": 0}, {**CLOSED, "outstanding": 0},
    {**CLOSED, "buckets": []}])
def test_mix_rejects_malformed_parameters(bad):
    with pytest.raises(traffic.TrafficError):
        traffic.Mix.parse(bad)


def _op(name, s, e, custom=False):
    return xtrace.Op(name, s, e, custom)


def test_reduction_of_hand_made_ops():
    ops = [_op("fusion.1", 0.10, 0.20), _op("kernel", 0.15, 0.30, True),
           _op("copy", 0.50, 0.60), _op("late", 0.95, 1.20)]
    tr = xtrace.DeviceTrace({"/device:TPU:0": ops},
                            {"/device:TPU:0": [_op("jit_f", 0.1, 0.3),
                                               _op("jit_f", 1.5, 1.6)]})
    red = xtrace.reduce(tr, 0.0, 1.0)
    assert red.busy_s == pytest.approx(0.20 + 0.10 + 0.05)
    assert red.custom_s == pytest.approx(0.15)
    assert red.xla_s == pytest.approx(0.10 + 0.10 + 0.05)
    assert red.executions == 1
    assert red.gaps == pytest.approx([(0.0, 0.1), (0.3, 0.5), (0.6, 0.95)])
    spans = [("finalize", 0.29, 0.45), ("dispatch", 0.44, 0.46),
             ("device", 0.0, 0.2), ("queue", 0.55, 0.95)]
    tags = xtrace.tag_gaps(red.gaps, spans)
    assert tags["device"] == pytest.approx([0.1, 1])
    assert tags["finalize"] == pytest.approx([0.2, 1])
    assert tags["idle"] == pytest.approx([0.35, 1])
    assert xtrace.reduce(tr, 2.0, 3.0) is None


@pytest.mark.parametrize("name,stats,custom", [
    ("custom-call.12", {}, True),
    ("mbconv_kernel", {"hlo_category": "custom-call"}, True),
    ("fusion.3", {"long_name": "%fusion.3 = f32[8] fusion(...)"}, False),
    ("copy.1", {"hlo_category": "data formatting"}, False)])
def test_custom_call_classification(name, stats, custom):
    assert xtrace.is_custom_call(name, stats) is custom


def test_tpu_op_names_are_shortened():
    name = ('%supersite_op.2 = f32[8,64,56,32]{3,2,1,0:T(8,128)S(1)} '
            'custom-call(f32[8,2,69,112,16]{4,3,2,1,0:T(8,128)} %pad), '
            'custom_call_target="tpu_custom_call"')
    assert xtrace.short_name(name) == \
        "supersite_op.2 custom-call f32[8,64,56,32]"
    assert xtrace.is_custom_call(name, {})
    tupled = ('%_supersite_op_int8.2 = (s8[8,56,56,32]{3,2,1,0:T(8,128)'
              '(4,1)S(1)}, f32[8]{0:T(256)}) custom-call(s8[8,2,69,112,16]'
              '{4,3,2,1,0:T(8,128)(4,1)} %pad, f32[8]{0} %scale), '
              'custom_call_target="tpu_custom_call"')
    assert xtrace.short_name(tupled) == \
        "_supersite_op_int8.2 custom-call (s8[8,56,56,32], f32[8])"
    assert xtrace.is_custom_call(tupled, {})
    assert xtrace.short_name("while.86") == "while.86"
