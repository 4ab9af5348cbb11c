"""Device time of the FusedMBConv kernels (ops named ``fmbconv_op*``)
per program execution, from the profiler's device trace.  None where
the trace holds no such op (a program without the kernel).  Layer:
kernels (``kernels/fmbconv``)."""

KERNEL = "fmbconv_op"


def seconds(run) -> float:
    """Device seconds of the kernel's ops in the window."""
    return sum(s for name, s in run.device.by_op.items()
               if name.startswith(KERNEL))


def read(run):
    n = run.forwards()
    if run.device is None or not n:
        return None
    s = seconds(run)
    return s * 1e3 / n if s else None
