"""Device time of Pallas kernels (custom calls) per program execution,
from the profiler's device trace.  Layer: kernels (``kernels/*``)."""


def read(run):
    n = run.forwards()
    if run.device is None or not n:
        return None
    return run.device.custom_s * 1e3 / n
