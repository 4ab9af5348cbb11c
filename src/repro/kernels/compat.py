"""Settings shared by every Pallas kernel: compiler params, VMEM sizes,
and the interpret-mode switch.

``default_interpret`` resolves a kernel wrapper's ``interpret=None``:
compiled Mosaic kernels on a TPU backend, the Pallas interpreter only
when the backend is the CPU (the test suite's ``JAX_PLATFORMS=cpu``).
Any other backend is an error rather than a silent interpreter run, so
a misconfigured accelerator process cannot pass for a chip run.

VMEM has two numbers.  ``VMEM_BUDGET_BYTES`` is the planner's analytic
per-launch budget (``KernelImpl.vmem_bytes`` against it decides which
sites fuse and how tall a super-site band is); the analytic models
count logical bytes.  ``VMEM_LIMIT_BYTES`` is the scoped-VMEM limit the
compiler is given: Mosaic pads channel-last blocks to 128 lanes and
spills large in-register values, so a launch inside the analytic
budget can need several times more.  Half of a v5e's 128 MiB VMEM
covers every B1 kernel at 224 px (``tests/test_chip_compile.py``).  At
384 px it does not cover the stem DSConv, whose whole-map block of
C = 16 channels pads to 128 lanes: Mosaic asks for 118 MiB (fp) and
98 MiB (int8) at batch 8, and the planner's logical count cannot see it.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET_BYTES = 8 * 1024 * 1024
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def tpu_compiler_params(**kwargs):
    """Mosaic compiler params with the shared scoped-VMEM limit."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES, **kwargs)


@functools.lru_cache(maxsize=None)
def _backend() -> str:
    return jax.default_backend()


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` kwarg: explicit bools pass through,
    ``None`` means "compile on TPU, interpret on CPU"."""
    if interpret is not None:
        return interpret
    backend = _backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas backend for platform {backend!r}: "
                       f"kernels compile for 'tpu' and interpret on 'cpu'")
