"""99th percentile of how long a request of the window waited before
its batch formed: from its due time (not its admission, which a busy
host loop delays) to the end of its last ``queue`` span, the
scheduler's own span of queue residency.  Layer: scheduler."""
from benchlib.system import percentile


def read(run):
    rid_of = {s[4]: s[3].get("rid") for s in run.spans if s[0] == "request"}
    left = {}
    for name, _start, end, _attrs, _sid, parent in run.spans:
        if name == "queue" and parent in rid_of:
            rid = rid_of[parent]
            left[rid] = max(left.get(rid, end), end)
    waits = [(left[s.rid] - s.due) * 1e3 for s in run.window
             if s.rid in left]
    return percentile(waits, 99) if waits else None
