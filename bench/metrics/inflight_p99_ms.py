"""99th percentile of a batch's time in flight, from dispatch to its
output read back on the host: the scheduler's ``device`` spans that
open inside the window.  Layer: scheduler to device."""
from benchlib.system import percentile


def read(run):
    d = [(end - start) * 1e3
         for _n, start, end, *_ in run.spans_in_window("device")]
    return percentile(d, 99) if d else None
