"""The trace reduction on a real profile recorded on the CPU: the clocks
align (a host span around a sleep names the device gap under it), busy
and idle add up to the window, and per-op time is read."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import xtrace  # noqa: E402


def test_cpu_profile_reduction(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.sin(a @ a.T).sum())
    x = jnp.ones((384, 384)) * 0.01
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        align = time.perf_counter()
        with jax.profiler.TraceAnnotation(xtrace.ALIGN):
            pass
        spans = []
        t0 = time.perf_counter()
        for _ in range(4):
            a = time.perf_counter()
            f(x).block_until_ready()
            b = time.perf_counter()
            time.sleep(0.02)
            spans += [("dispatch", a, b), ("finalize", b, time.perf_counter())]
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    trace = xtrace.load(xtrace.find_xplane(str(tmp_path)), align)
    red = xtrace.reduce(trace, t0, t1)
    assert red is not None and red.window_s == pytest.approx(t1 - t0)
    assert 0 < red.busy_s < red.window_s
    idle = sum(e - s for s, e in red.gaps)
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-6)
    assert red.xla_s > 0 and red.custom_s == 0
    assert any("dot" in name for name in red.by_op)
    tags = xtrace.tag_gaps(red.gaps, spans)
    assert tags["finalize"][0] >= 4 * 0.019
    assert tags["finalize"][0] > 0.8 * idle
