"""Device time of the ResBlock stem kernel (ops named ``resblock_op*``)
per program execution, from the profiler's device trace.  None where
the trace holds no such op (a program without the kernel).  Layer:
kernels (``kernels/resblock``)."""

KERNEL = "resblock_op"


def read(run):
    n = run.forwards()
    if run.device is None or not n:
        return None
    s = sum(s for name, s in run.device.by_op.items()
            if name.startswith(KERNEL))
    return s * 1e3 / n if s else None
