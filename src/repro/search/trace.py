"""Recorded traffic traces: the workload a schedule is searched against.

A trace is the minimal record of real (or benchmark-synthesized)
traffic: ``[(arrival_seconds, resolution), ...]`` in arrival order.
``benchmarks/serving_bench.py --record-trace`` exports one; the offline
search (``repro.search.drivers``) replays it through ``workload()`` —
a deterministic host-side mirror of the serving scheduler's batch
formation — to learn how often each (bucket, resolution) executor
would actually dispatch.  Occupancy-weighting the cycle-model objective
by those counts is what makes the searched schedule specific to the
traffic it will serve, CHOSEN-style, instead of to a uniform shape mix.

Versioned like every artifact here: a trace file carries
``TRACE_SCHEMA`` and loading rejects a mismatch (typed
``ArtifactError``) rather than silently reinterpreting old bytes.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
from typing import List, Mapping, Sequence, Tuple

from repro.common.errors import ArtifactError

__all__ = ["TRACE_SCHEMA", "save_trace", "load_trace",
           "trace_fingerprint", "workload"]

TRACE_SCHEMA = 1


def _canonical(trace) -> List[Tuple[float, int]]:
    out = []
    for at, res in trace:
        at, res = float(at), int(res)
        assert at >= 0 and res > 0, (at, res)
        out.append((at, res))
    return out


def trace_fingerprint(trace) -> str:
    """Stable content hash of a trace (hex, 16 chars): artifacts pin the
    trace they were searched against so a schedule tuned for one traffic
    mix is never mistaken for another's."""
    payload = json.dumps(_canonical(trace), separators=(",", ":"))
    return hashlib.blake2b(payload.encode(), digest_size=8).hexdigest()


def save_trace(path: str, trace, *, spec: Mapping | None = None) -> str:
    """Write a trace JSON (schema-stamped, atomic replace); returns the
    fingerprint.  ``spec`` rides along as provenance (the generating
    benchmark's knobs) — load ignores it."""
    reqs = _canonical(trace)
    doc = {"schema": TRACE_SCHEMA, "fingerprint": trace_fingerprint(reqs),
           "requests": [[at, res] for at, res in reqs]}
    if spec is not None:
        doc["spec"] = {k: v if isinstance(v, (int, float, str, bool))
                       else list(v) for k, v in spec.items()}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return doc["fingerprint"]


def load_trace(path: str) -> List[Tuple[float, int]]:
    """Read a trace JSON; raises ``ArtifactError`` on a schema-version
    mismatch or a structurally invalid file."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise ArtifactError(f"trace {path!r} unreadable: {e}") from e
    if not isinstance(doc, dict) or doc.get("schema") != TRACE_SCHEMA:
        got = doc.get("schema") if isinstance(doc, dict) else None
        raise ArtifactError(
            f"trace {path!r} has schema {got!r}, expected {TRACE_SCHEMA} "
            f"— re-record it with the current serving_bench")
    try:
        return _canonical(doc["requests"])
    except (KeyError, TypeError, ValueError, AssertionError) as e:
        raise ArtifactError(f"trace {path!r} malformed: {e}") from e


def workload(trace, buckets: Sequence[int], *,
             deadline_ms: float | None = None) -> dict:
    """Dispatch counts per (bucket, resolution) under the serving
    runtime's bucketed batch formation — the occupancy weights of the
    search objective.

    This deterministically mirrors ``benchmarks/serving_bench.replay``:
    one scheduler step per arrival (full largest buckets dispatch
    immediately, a deadline-due tail flushes to the smallest covering
    bucket), then the straggler step after the deadline elapses, then
    the final drain.  Uses the scheduler's own ``form_batches``,
    so the model cannot drift from what serving actually does —
    ``tests/test_search.py`` pins the smoke trace's key set to
    ``serving_bench.EXPECTED_SMOKE_KEYS``.
    """
    from repro.serving.scheduler import form_batches

    buckets = tuple(sorted(set(int(b) for b in buckets)))
    assert buckets and buckets[0] >= 1, buckets
    queues: dict[int, collections.deque] = {}
    counts: dict[Tuple[int, int], int] = collections.Counter()

    def step(now: float, drain: bool = False) -> None:
        for res, q in queues.items():
            due = drain or (deadline_ms is not None and any(
                now >= at + deadline_ms / 1e3 for at in q))
            for size in form_batches(len(q), buckets, due):
                take = min(size, len(q))
                if take == 0:
                    break
                for _ in range(take):
                    q.popleft()
                counts[(size, res)] += 1

    clock = 0.0
    for at, res in _canonical(trace):
        clock = max(clock, at)
        queues.setdefault(res, collections.deque()).append(at)
        step(clock)
    if deadline_ms is not None:
        clock += deadline_ms / 1e3
    step(clock)
    step(clock, drain=True)
    assert not any(queues.values()), "workload model dropped requests"
    return dict(counts)
