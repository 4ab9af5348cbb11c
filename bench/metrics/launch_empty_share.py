"""Share of the launches that found no batch in flight, in %: of the
scheduler's ``launch`` spans (children of ``dispatch``: the executor
call) that open inside the window, did not raise and carry the
``inflight`` attribute (the batches already in flight at the launch),
those with ``inflight`` 0.  A launch into an empty pipeline is one the
device waited for.  No reading where no launch carries the attribute.
Layer: scheduler to device."""


def read(run):
    inflight = [attrs["inflight"]
                for _n, _s, _e, attrs, *_ in run.spans_in_window("launch")
                if "error" not in attrs and "inflight" in attrs]
    if not inflight:
        return None
    return 100.0 * sum(1 for n in inflight if n == 0) / len(inflight)
