"""Time in the window with no batch in flight, over the batches
dispatched in it: the host clock's measure of the device having nothing
enqueued.  A batch is in flight from the end of its ``launch`` span (the
executor call returned) to the end of its ``readback`` span, whether the
read succeeded or raised; a launch that raised enqueued nothing.  Spans
that opened before the window count, clipped to it.  Layer: scheduler
to device."""


def read(run):
    batches = len(run.spans_in_window("dispatch"))
    steps = [(end, 1) for name, _s, end, attrs, *_ in run.spans
             if name == "launch" and "error" not in attrs]
    if not batches or not steps:
        return None
    steps += [(end, -1) for name, _s, end, *_ in run.spans
              if name == "readback"]
    empty, level, since = 0.0, 0, run.t0
    for t, step in sorted(steps):
        if t >= run.t1:
            break
        if t > since:
            if level <= 0:
                empty += t - since
            since = t
        level += step
    if level <= 0:
        empty += run.t1 - since
    return empty * 1e3 / batches
