"""Shared pytest fixtures.

NOTE: no XLA_FLAGS here on purpose — unit/smoke tests must see the real
single CPU device.  Distributed tests that need fake devices run
themselves in a subprocess (tests/test_distributed.py).
"""
import os
import signal
import sys

import pytest

# make tests/proptest.py importable regardless of invocation directory
sys.path.insert(0, os.path.dirname(__file__))

# Per-test wall-clock limit, seconds; 0 disables.  pytest-timeout is not
# in the container, so this is a SIGALRM equivalent: a wedged test (a
# hung compile, a scheduler that fails to drain) dies with a TimeoutError
# naming itself instead of stalling the whole CI job until the runner's
# global kill.  Main-thread only (SIGALRM), which is how this suite runs.
TEST_TIMEOUT_S = float(os.environ.get("REPRO_TEST_TIMEOUT", "600"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if TEST_TIMEOUT_S <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def _expire(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded REPRO_TEST_TIMEOUT="
            f"{TEST_TIMEOUT_S:.0f}s")

    old = signal.signal(signal.SIGALRM, _expire)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def tmp_autotune_cache(tmp_path, monkeypatch):
    """Isolated on-disk autotune cache (shared by the fusion test files)."""
    from repro.kernels import autotune as autotune_mod
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune_mod.clear_memory_cache()
    yield tmp_path / "at.json"
    autotune_mod.clear_memory_cache()


class _SiteTap:
    """``execute(profile=...)`` hook that keeps every site's output."""

    def __init__(self):
        self.out = {}

    def begin(self, site):
        pass

    def end(self, site, y):
        self.out[site.name] = y
        return y


@pytest.fixture
def int8_parity():
    """Check a fused int8 forward against the int8 reference where the
    two truly agree; returns the fused logits.

    Every int8 activation a fused producer emits must carry exactly the
    codes the reference's quantization of the same boundary gives: the
    integer arithmetic and the quantize decisions are identical.  The
    fp32 values around the codes are not bit-exact: a dequant epilogue
    ``acc * scale + bias`` rounds once when the compiler contracts it to
    an FMA (XLA's CPU fusions do) and twice when it does not (the Pallas
    interpreter), so per-image scales may differ in their last ulps
    (rtol 1e-6) and the fp32 tail (residual adds, head) carries those
    ulps to the logits at ~1e-7 relative (rtol 1e-5, atol 1e-7).  A
    flipped code moves a logit by orders of magnitude more.
    """
    import numpy as np
    from numpy.testing import assert_allclose

    from repro.core.program import execute
    from repro.core.quantization import QTensor, act_fp, quantize_act

    def check(program, qparams, x, plan):
        ref_tap, fus_tap = _SiteTap(), _SiteTap()
        ref = execute(program, qparams, x, profile=ref_tap)
        execute(program, qparams, x, plan=plan, profile=fus_tap)
        boundaries = 0
        for name, y in fus_tap.out.items():
            if isinstance(y, QTensor):
                want = quantize_act(act_fp(ref_tap.out[name]))
                np.testing.assert_array_equal(np.asarray(y.q),
                                              np.asarray(want.q), name)
                assert_allclose(np.asarray(y.scale), np.asarray(want.scale),
                                rtol=1e-6, err_msg=name)
                boundaries += 1
        assert boundaries, "no int8 boundary in the fused forward"
        fused = execute(program, qparams, x, plan=plan)   # super-sites on
        assert_allclose(np.asarray(fused), np.asarray(ref),
                        rtol=1e-5, atol=1e-7)
        return fused
    return check
