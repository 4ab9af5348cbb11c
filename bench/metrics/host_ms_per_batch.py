"""Host time of the scheduler per batch: the ``form``, ``dispatch`` and
``finalize`` spans that open inside the window, over the batches
dispatched in it.  Layer: scheduler host path."""


def read(run):
    batches = len(run.spans_in_window("dispatch"))
    if not batches:
        return None
    busy = sum(end - start for name in ("form", "dispatch", "finalize")
               for _n, start, end, *_ in run.spans_in_window(name))
    return busy * 1e3 / batches
