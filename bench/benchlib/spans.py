"""Span arithmetic shared by the readers of the scheduler's spans
(``RunRecord.spans``: (name, start, end, attrs, span_id, parent_id) on
the host clock)."""


def ms_per_batch(run, name: str):
    """Time of the ``name`` spans that open inside the window, in ms,
    over the batches dispatched in it; None where the program records no
    such span."""
    spans = run.spans_in_window(name)
    batches = len(run.spans_in_window("dispatch"))
    if not spans or not batches:
        return None
    return sum(end - start for _n, start, end, *_ in spans) * 1e3 / batches
