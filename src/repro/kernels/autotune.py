"""Block-size autotuner for the Pallas kernels, with a persistent cache.

The fused kernels tile their grids by ``block_n`` / ``block_f`` /
``block_{m,k}``; the best tile depends on (shape, dtype, backend) — the
same compile-time search CHOSEN (arXiv 2407.12736) runs over its FPGA
design points.  ``autotune()`` sweeps a candidate list by timing the real
kernel and remembers the winner in an on-disk JSON cache, so the sweep
runs once per (kind, key) per machine and every later process — including
a fresh interpreter — reuses the choice without re-timing.  Callers put
the dtype in the key next to the backend ("f32" vs "i8"), so the FIX8
kernels tune and cache their tiles independently of the fp32 ones.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.

Inside a ``jax.jit`` trace there is nothing to time, so callers that may
be under tracing pass ``bench=None`` and get the cached choice or the
first (heuristic-default) candidate.  ``repro.core.fusion.plan_program``
tunes ahead of time, outside jit, which is where the sweeps actually run.
A sweep in which every candidate fails raises on a compiled backend (the
chip's compiler refused every tile) and falls back to the default only
in the interpreter.

The module also owns ``pad_to_multiple`` — the supported way to handle
ragged shapes.  Kernels used to silently fall back to one full-tensor
block whenever ``N % block != 0``; now the wrapper pads the ragged axis
up to the tile boundary (zeros are exact for matmul accumulation and for
ReLU-gated attention state) and slices the output back.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.compat import default_interpret

__all__ = ["autotune", "shape_key", "pad_to_multiple", "tile_work",
           "cache_path", "clear_memory_cache", "set_fault_hook",
           "export_entries", "import_entries", "SWEEP_COUNT",
           "AUTOTUNE_SCHEMA"]

# On-disk cache schema version.  The file is a flat {key: choice} dict
# plus one reserved ``_SCHEMA_KEY`` row carrying {"version": N}.  A file
# whose version is missing or different was written by another era of
# the key/candidate encoding: silently deserializing it would hand
# kernels stale block choices under reinterpreted keys, so mismatches
# are REJECTED with a warning (affected shapes re-tune; the next save
# rewrites the file at the current schema).  Bump this whenever
# ``shape_key`` fields or choice-dict semantics change.
AUTOTUNE_SCHEMA = 2
_SCHEMA_KEY = "__schema__"

# in-memory cache: {cache_key: choice-dict}; mirrors the on-disk file
_MEM: dict[str, dict] = {}
_DISK_LOADED: set[str] = set()

# failure-injection hook (serving.faults.FaultPlan.install): called as
# hook(kind, key) at the top of every autotune() consultation, so chaos
# tests can make a sweep crash deterministically.  None in production.
_FAULT_HOOK: Callable[[str, Sequence], None] | None = None


def set_fault_hook(hook: Callable[[str, Sequence], None] | None) -> None:
    global _FAULT_HOOK
    _FAULT_HOOK = hook

# number of timed sweeps this process has run (tests assert cache hits
# by checking this does not grow on a reload)
SWEEP_COUNT = 0


def cache_path() -> str:
    p = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def clear_memory_cache() -> None:
    """Drop the in-process cache (tests use this to force a disk reload)."""
    _MEM.clear()
    _DISK_LOADED.clear()


def _read_cache_file(path: str) -> dict:
    """Parse the cache file into {key: choice-dict}, tolerating damage.

    A corrupt or truncated file (killed process mid-write before atomic
    replace existed, disk damage, hand edits) must cost a warning and a
    re-tune, never a crash: a poisoned cache would otherwise take down
    every later process on this machine.  Malformed entries are dropped
    individually so one bad row doesn't discard a whole valid cache.
    """
    try:
        with open(path) as f:
            raw = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        warnings.warn(
            f"autotune cache {path!r} is corrupt ({e!r}); ignoring it — "
            f"affected shapes will re-tune and the next save rewrites "
            f"the file atomically", RuntimeWarning, stacklevel=3)
        return {}
    if not isinstance(raw, dict):
        warnings.warn(
            f"autotune cache {path!r} holds {type(raw).__name__}, not a "
            f"dict; ignoring it", RuntimeWarning, stacklevel=3)
        return {}
    schema = raw.pop(_SCHEMA_KEY, None)
    version = schema.get("version") if isinstance(schema, dict) else None
    if version != AUTOTUNE_SCHEMA:
        warnings.warn(
            f"autotune cache {path!r} has schema version {version!r} but "
            f"this build expects {AUTOTUNE_SCHEMA}; rejecting the cache — "
            f"affected shapes will re-tune and the next save rewrites the "
            f"file at the current schema", RuntimeWarning, stacklevel=3)
        return {}
    bad = [k for k, v in raw.items() if not isinstance(v, dict)]
    if bad:
        warnings.warn(
            f"autotune cache {path!r}: dropping {len(bad)} malformed "
            f"entries (first: {bad[0]!r})", RuntimeWarning, stacklevel=3)
    return {k: v for k, v in raw.items() if isinstance(v, dict)}


def _load_disk(path: str) -> None:
    if path in _DISK_LOADED:
        return
    _DISK_LOADED.add(path)
    _MEM.update(_read_cache_file(path))


def _save_disk(path: str) -> None:
    try:
        # merge under the current disk state so concurrent processes
        # tuning different shapes don't drop each other's entries
        merged = _read_cache_file(path)
        merged.update(_MEM)
        merged[_SCHEMA_KEY] = {"version": AUTOTUNE_SCHEMA}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # atomic publish: write a private temp file, fsync it, then
        # rename over the target — a process killed at ANY point leaves
        # either the old complete cache or the new complete cache on
        # disk, never a truncated file later runs would choke on
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: keep the in-memory cache only


def _key(kind: str, key: Sequence) -> str:
    return f"{kind}|" + ",".join(str(k) for k in key)


def shape_key(*, batch: int, spatial, dtype: str, backend: str,
              **dims) -> tuple:
    """Canonical persistent-cache key for a kernel tuning case.

    Every key MUST carry the batch size and the spatial extent(s): the
    serving runtime lowers the same network at several (batch bucket,
    resolution) pairs, and a key that only encoded channels + dtype
    would hand one bucket's block choice to a different shape — a stale
    tile that silently mis-sizes the grid.  ``batch`` is whatever the
    kernel grids over (the image batch for the conv megakernels, the
    folded branch*batch*head axis for attention); ``spatial`` is the
    per-sample extent (H, W) or a token count.  Labeled ``name=value``
    items keep the on-disk key self-describing, so dropping a dimension
    or reordering fields cannot re-introduce a collision unnoticed.
    """
    try:
        spatial = tuple(int(s) for s in spatial)
    except TypeError:
        spatial = (int(spatial),)
    parts = [f"b={int(batch)}", "s=" + "x".join(str(s) for s in spatial)]
    parts += [f"{k}={v}" for k, v in sorted(dims.items())]
    parts += [f"dtype={dtype}", f"backend={backend}"]
    return tuple(parts)


def _time_once(fn: Callable[[], object], reps: int = 3) -> float:
    jax.block_until_ready(fn())          # warm-up / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(kind: str, key: Sequence, candidates: Sequence[dict],
             bench: Callable[[dict], object] | None = None, *,
             interpret: bool | None = None) -> dict:
    """Pick the fastest candidate block config for (kind, key).

    kind:       kernel family, e.g. "relu_attn" / "mbconv" / "int8_matmul"
    key:        hashable shape/dtype/backend tuple identifying the case
    candidates: list of kwargs dicts (e.g. [{"block_n": 128}, ...])
    bench:      callable(candidate) -> result; timed via block_until_ready.
                None (e.g. under jit tracing) -> cached choice or
                candidates[0] without sweeping.
    interpret:  whether ``bench`` runs the Pallas interpreter; None
                resolves it from the backend (``default_interpret``), so
                an omitted flag on a TPU never selects the quiet path.

    A candidate whose bench raises is disqualified with a warning that
    names it and its exception, so candidate lists can include tiles
    that exceed VMEM for some shapes.  When every candidate fails, the
    interpreter falls back to ``candidates[0]`` (uncached); a compiled
    backend raises ``PlanError`` instead: there the failures are the
    chip's compiler refusing every tile, which a default would only
    hide until the first launch.
    """
    global SWEEP_COUNT
    assert candidates, "autotune needs at least one candidate"
    if _FAULT_HOOK is not None:
        _FAULT_HOOK(kind, key)
    path = cache_path()
    _load_disk(path)
    ck = _key(kind, key)
    hit = _MEM.get(ck)
    if hit is not None:
        return dict(hit)
    if bench is None:
        return dict(candidates[0])

    SWEEP_COUNT += 1
    best_t, best_c = float("inf"), None
    failures = []
    for cand in candidates:
        try:
            t = _time_once(lambda: bench(cand))
        except Exception as e:
            failures.append(f"{cand}: {e!r}")
            warnings.warn(f"autotune {ck}: candidate {cand} disqualified: "
                          f"{e!r}", RuntimeWarning, stacklevel=2)
            continue
        if t < best_t:
            best_t, best_c = t, dict(cand)
    if best_c is None:
        if not default_interpret(interpret):
            from repro.common.errors import PlanError
            raise PlanError(f"autotune {ck}: every candidate failed on the "
                            f"compiled backend: " + "; ".join(failures))
        return dict(candidates[0])   # interpreter: fall back, don't cache
    _MEM[ck] = best_c
    _save_disk(path)
    return dict(best_c)


def export_entries() -> dict:
    """Snapshot the tuner cache as {cache_key: choice-dict}.

    The offline schedule search (``repro.search``) embeds this in its
    ``ScheduleArtifact`` so a cold-start pod can seed the tuner without
    running a single sweep.  Loads the disk cache first so the export
    sees everything this machine has ever tuned, not just this process.
    """
    _load_disk(cache_path())
    return {k: dict(v) for k, v in _MEM.items()}


def import_entries(entries: dict, *, persist: bool = False) -> int:
    """Seed the tuner cache from an exported snapshot; returns the count
    adopted.  Imported choices win over whatever is already in memory —
    an artifact's tuned blocks are the point of shipping it.  With
    ``persist`` the merged cache is also written to disk."""
    good = {k: dict(v) for k, v in entries.items()
            if isinstance(k, str) and isinstance(v, dict)
            and k != _SCHEMA_KEY}
    path = cache_path()
    _load_disk(path)
    _MEM.update(good)
    if persist and good:
        _save_disk(path)
    return len(good)


def tile_work(n: int, block: int) -> float:
    """Relative overcompute (>= 1.0) of covering an ``n``-extent axis
    with ``block``-wide tiles: the padded ragged tail is dead work the
    grid still executes.  The device-free block score of the offline
    schedule search (``KernelImpl.block_work``)."""
    import math
    n, block = int(n), int(block)
    assert n > 0 and block > 0, (n, block)
    return math.ceil(n / block) * block / n


def pad_to_multiple(x: jax.Array, axis: int, multiple: int):
    """Zero-pad ``x`` along ``axis`` up to a multiple; returns (padded, n).

    ``n`` is the original length, for slicing the kernel output back.
    Zero padding is exact for every tiled kernel here: int8/fp32 matmul
    accumulation ignores zero rows, and ReLU-gated attention maps zero
    tokens to zero KV-state and zero divisor contributions.
    """
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths), n
