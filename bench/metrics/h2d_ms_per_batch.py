"""Host time of the host->device copy of each input batch: the
scheduler's ``h2d`` spans (children of ``dispatch``) that open inside
the window, over the batches dispatched in it.  Layer: scheduler host
path."""
from benchlib.spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "h2d")
