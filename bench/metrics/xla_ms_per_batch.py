"""Device time of XLA's own operations (everything but Pallas custom
calls: weight packing, pads, quantization, copies, fusions) per
program execution, from the profiler's device trace.  Layer: program
(``core/program.py``, ``core/fusion.py``)."""


def read(run):
    n = run.forwards()
    if run.device is None or not n:
        return None
    return run.device.xla_s * 1e3 / n
