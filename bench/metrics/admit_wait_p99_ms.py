"""99th percentile (nearest rank) of how long a client waited for the
scheduler's lock in ``submit``: the ``admit`` spans that open inside the
window.  ``finalize`` holds that lock while it blocks on the device, so
this is the client's share of a blocked host loop.  Layer: scheduler
admission."""
from benchlib.system import percentile


def read(run):
    d = [(end - start) * 1e3
         for _n, start, end, *_ in run.spans_in_window("admit")]
    return percentile(d, 99) if d else None
