"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps, on the host clock of the run.

Device operations are the events of the ``XLA Ops`` line of every
``/device:*`` plane.  A trace with no device plane (the CPU backend)
falls back to host events that carry an ``hlo_op`` stat, which is how
XLA's CPU client reports the ops it runs; that is for checking this
code, never a device reading.  An operation is a Pallas kernel when it
is a custom call (its name, category or long name says so); everything
else is XLA's own code.

Times in a trace are nanoseconds from the start of the profiling
session.  The harness writes one ``TraceAnnotation`` (``ALIGN``) at a
host-clock reading it keeps; that event puts the trace on the host's
clock, so the run's own spans can name what the host was doing in each
device gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

ALIGN = "bench.clock_align"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float        # seconds on the host clock
    end: float
    custom: bool        # a custom call: a Pallas kernel on the TPU


@dataclasses.dataclass
class DeviceTrace:
    ops: dict                    # plane name -> [Op] (sorted by start)
    modules: dict                # plane name -> [Op] program executions


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (AttributeError, TypeError, ValueError):
        return {}


_SHAPE = r"\w+\[[^\]]*\]"
_HLO = re.compile(rf"%?([\w.\-]+) = ({_SHAPE}|\(.*?\))\S* ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8,56,56,32]{...} fusion(...), ...`` (how the TPU
    names an op: its HLO text) -> ``fusion.3 fusion f32[8,56,56,32]``;
    a tuple result ``(s8[8,56,56,32]{...}, f32[8]{...})`` keeps its
    shapes, ``(s8[8,56,56,32], f32[8])``."""
    m = _HLO.match(name)
    if not m:
        return name
    shape = m[2]
    if shape.startswith("("):
        shape = f"({', '.join(re.findall(_SHAPE, shape))})"
    return f"{m[1]} {m[3]} {shape}"


def is_custom_call(name: str, stats: dict) -> bool:
    text = " ".join(str(stats.get(k, "")) for k in
                    ("hlo_category", "long_name", "tf_op", "hlo_op"))
    text = f"{name} {text}".lower().replace("_", "-")
    return "custom-call" in text or "tpu-custom-call" in text


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, align_host_s: float) -> DeviceTrace:
    """Device ops and program executions of a trace, on the host clock:
    ``align_host_s`` is the host-clock reading taken as the ``ALIGN``
    annotation opened."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = list(pd.planes)
    align_ns = None
    for plane in planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ALIGN:
                    align_ns = ev.start_ns
    if align_ns is None:
        raise ValueError(f"{path}: no {ALIGN} event to align the clocks")
    off = align_host_s - align_ns * 1e-9

    seen = {}        # raw op name -> (short name, custom call?)

    def op(ev, stats=None):
        if ev.name not in seen:
            seen[ev.name] = (short_name(ev.name), is_custom_call(
                ev.name, _stats(ev) if stats is None else stats))
        name, custom = seen[ev.name]
        start = ev.start_ns * 1e-9 + off
        return Op(name, start, start + ev.duration_ns * 1e-9, custom)

    ops, modules = {}, {}
    devices = [p for p in planes if p.name.startswith("/device:")]
    for plane in devices:
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops[plane.name] = sorted((op(ev) for ev in line.events),
                                         key=lambda o: o.start)
            elif line.name == MODULES_LINE:
                modules[plane.name] = sorted(
                    (op(ev, {}) for ev in line.events),
                    key=lambda o: o.start)
    if not ops:              # CPU backend: ops carry an hlo_op stat
        host = []
        for plane in planes:
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        host.append(op(ev, st))
        if host:
            ops["host"] = sorted(host, key=lambda o: o.start)
    return DeviceTrace(ops, modules)


def clip(ops, t0: float, t1: float):
    """The parts of ``ops`` inside [t0, t1)."""
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(dataclasses.replace(o, start=s, end=e))
    return out


def union(ops):
    """Merged busy intervals [(start, end)] of ``ops``."""
    merged = []
    for o in sorted(ops, key=lambda o: o.start):
        if merged and o.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], o.end)
        else:
            merged.append([o.start, o.end])
    return [tuple(m) for m in merged]


def gaps(busy, t0: float, t1: float):
    """Idle intervals of [t0, t1) between the busy ones."""
    out, t = [], t0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t1 > t:
        out.append((t, t1))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float               # mean over the device planes
    custom_s: float             # Pallas kernels, summed over planes
    xla_s: float                # every other op, summed over planes
    executions: int             # program executions started in window
    by_op: dict                 # op name -> seconds
    gaps: list                  # [(start, end)] idle on the first plane


def reduce(trace: DeviceTrace, t0: float, t1: float) -> Reduction | None:
    """Everything the per-layer readers take from the device, over the
    window [t0, t1) of the host clock; None when the trace holds no
    device operation there."""
    planes = {k: clip(v, t0, t1) for k, v in trace.ops.items()}
    planes = {k: v for k, v in planes.items() if v}
    if not planes:
        return None
    busy, by_op, custom, xla = [], {}, 0.0, 0.0
    for ops in planes.values():
        busy.append(sum(e - s for s, e in union(ops)))
        for o in ops:
            d = o.end - o.start
            by_op[o.name] = by_op.get(o.name, 0.0) + d
            if o.custom:
                custom += d
            else:
                xla += d
    first = sorted(planes)[0]
    execs = sum(1 for mods in trace.modules.values() for m in mods
                if t0 <= m.start < t1)
    return Reduction(window_s=t1 - t0, busy_s=sum(busy) / len(busy),
                     custom_s=custom, xla_s=xla, executions=execs,
                     by_op=by_op,
                     gaps=gaps(union(planes[first]), t0, t1))


# what the host was doing, most specific first
HOST_TAGS = ("finalize", "dispatch", "form", "device")


def tag_gaps(gap_list, spans):
    """Name each idle gap by the scheduler span that overlaps it most
    (``spans``: (name, start, end) on the host clock); ``"device"`` is a
    batch in flight whose output the host has not read yet, ``"idle"``
    no scheduler span at all.  Returns {tag: [seconds, count]}."""
    spans = sorted((s for s in spans if s[0] in HOST_TAGS),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    out = {}
    for g0, g1 in gap_list:
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        cands = [(min(e, g1) - max(s, g0), -HOST_TAGS.index(name), name)
                 for name, s, e in spans[lo:hi]]
        best = max((c for c in cands if c[0] > 0), default=None)
        acc = out.setdefault("idle" if best is None else best[2], [0.0, 0])
        acc[0] += g1 - g0
        acc[1] += 1
    return out
