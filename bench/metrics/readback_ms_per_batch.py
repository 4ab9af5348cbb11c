"""How long the host waited on the device's answer per batch: the
scheduler's ``readback`` spans (children of ``device``: the blocking
read of a batch's output in ``finalize``) that open inside the window,
over the batches dispatched in it.  Layer: scheduler to device."""
from benchlib.spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "readback")
