"""Shape-bucketed executor cache over the Program IR.

The paper's reconfigurable engine keeps ONE compiled schedule busy
across heterogeneous ops (TMP dataflow, §III/§IV); the serving-system
analogue is keeping a small set of compiled executables busy across
heterogeneous *requests*.  CHOSEN (arXiv 2407.12736) builds exactly
this specialize-per-shape compilation layer for ViT inference; ME-ViT
(arXiv 2402.09709) quantifies how much throughput leaks when batch
shaping and memory movement are left to chance.

An ``Executor`` is one fully specialized pipeline for an
``ExecutorKey = (batch bucket, resolution, precision)``:

    lower(cfg, batch, image_size)   -> Program     (cached, per shape)
    plan_program(program, params)   -> FusionPlan  (autotune swept ONCE,
                                       outside the request loop; block
                                       choices inherited from a donor
                                       bucket at the same resolution via
                                       ``plan_program(..., reuse=)``)
    jax.jit(execute)                -> the compiled forward

``ExecutorCache`` builds executors lazily on first use, serves them LRU
with optional capacity eviction, exposes ``warmup`` (pre-compile the
expected working set before traffic arrives) and reports cache behavior
(hits / misses / plan reuse / evictions) into a shared ``Telemetry``.

## Fault tolerance

Serve-time compiles can fail (and, under a ``serving.faults.FaultPlan``,
are *made* to fail), so the cache is hardened:

  * a failed ``lower`` -> ``plan`` -> ``jit`` build never leaves a
    half-built entry — nothing is inserted until the build succeeds,
    a failed entry's donor plan is never published, and a warmed entry
    whose compile crashes is evicted;
  * build failures are **negative-cached** for ``neg_ttl_s`` seconds:
    a hot failing bucket raises a cheap typed ``ExecutorError`` on every
    request instead of re-running the whole compile pipeline each time;
  * each key carries a **degradation ladder** (``DegradeState``): level
    0 is the normal fused plan, ``degrade(site=...)`` replans with the
    blamed site demoted to the reference path (``"vmem"``-style, reason
    ``"fault"``), a further ``degrade`` drops to the reference IR
    interpreter (``plan=None``), and ``pin_fp`` rebuilds the plan at
    forced-fp precision — the response to an int8 numerics blow-up.
    Degraded keys stop donating plans and rebuild on next use.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.errors import ExecutorError, MeshExhausted, ReproError
from repro.core.efficientvit import EfficientViTConfig
from repro.core.fusion import plan_program
from repro.core.program import execute, lower
from repro.serving.sharding import DeviceHealth, sharded_forward
from repro.serving.telemetry import Telemetry

__all__ = ["ExecutorKey", "Executor", "ExecutorCache", "DegradeState"]


def _plan_routes(plan) -> dict:
    """Which path each planned site takes, by kind.

    ``attrs`` (the ``plan`` span's closing attributes): ``fused_<kind>``
    and ``ref_<kind>`` site counts, plus ``demoted``, the reference-path
    sites as ``site:reason`` in program order ("" when none).
    ``counters`` (``Telemetry``): ``plan_sites_fused.<kind>`` and
    ``plan_sites_ref.<kind>.<reason>``.  Super-site members count under
    their own kind.
    """
    attrs: dict = {}
    counters: dict = {}
    demoted = []
    for d in plan.decisions.values():
        route = "fused" if d.fused else "ref"
        attrs[f"{route}_{d.kind}"] = attrs.get(f"{route}_{d.kind}", 0) + 1
        name = (f"plan_sites_fused.{d.kind}" if d.fused
                else f"plan_sites_ref.{d.kind}.{d.reason}")
        counters[name] = counters.get(name, 0) + 1
        if not d.fused:
            demoted.append(f"{d.name}:{d.reason}")
    attrs["demoted"] = ",".join(demoted)
    return {"attrs": attrs, "counters": counters}


@dataclasses.dataclass(frozen=True)
class ExecutorKey:
    batch: int        # bucket size (the compiled batch dimension)
    resolution: int   # square image size
    precision: str    # requested plan precision: "auto" | "fp" | "int8"


@dataclasses.dataclass(frozen=True)
class DegradeState:
    """Where one executor key sits on the graceful-degradation ladder.

    ``level`` 0 = fully fused; 1 = the ``demoted`` sites replanned onto
    the reference path, everything else still fused; 2 = the whole key
    runs the reference IR interpreter (``plan=None`` semantics).
    ``pinned_fp`` forces the plan to ``precision="fp"`` — for a
    quantized tree every int8 kernel demotes to reference, which is the
    correctness-preserving response to an int8 numerics blow-up.
    """
    level: int = 0
    demoted: frozenset = frozenset()
    pinned_fp: bool = False

    @property
    def degraded(self) -> bool:
        return self.level > 0 or self.pinned_fp


class Executor:
    """One compiled (program, plan, jitted forward) for a fixed shape.

    ``program`` is the plan-annotated lowering (``Program.
    with_epilogues``): its sites carry the ``Epilogue`` each boundary
    actually delivers, which is what the serving benchmarks and the
    delivered-HBM accounting introspect.

    ``degraded`` is the key's ``DegradeState`` (None = healthy);
    ``faults`` is an optional ``serving.faults.FaultPlan`` consulted at
    dispatch: "kernel.launch" faults only fire on executors that
    actually launch fused kernels, and "epilogue.numerics" corruption
    only on executors running fused int8 sites — so a degraded rebuild
    genuinely escapes the failure it degraded away from.
    """

    def __init__(self, key: ExecutorKey, program, plan, *,
                 faults=None, degraded: Optional[DegradeState] = None,
                 fn=None, shard=None):
        self.key = key
        self.program = program.with_epilogues(plan) if plan is not None \
            else program
        self.plan = plan
        self.shard = shard   # ShardSpec when mesh-sharded, else None
        self._fn = fn if fn is not None else \
            jax.jit(lambda p, x: execute(program, p, x, plan=plan))
        self.calls = 0
        self.warmed = False
        self.faults = faults
        self.degraded = degraded
        decisions = plan.decisions.values() if plan is not None else ()
        self.fused_sites = tuple(d.name for d in decisions if d.fused)
        self._runs_int8 = any(d.fused and d.precision == "int8"
                              for d in decisions)

    @property
    def device_ids(self) -> Tuple[int, ...]:
        return self.shard.device_ids if self.shard is not None else ()

    def __call__(self, params, x):
        """Dispatch the compiled forward.  Asynchronous: the result is a
        device array; nothing blocks the host until someone reads it."""
        self.calls += 1
        if self.faults is not None and self.shard is not None:
            self.faults.fire(
                "device.dropout", batch=self.key.batch,
                resolution=self.key.resolution,
                precision=self.key.precision,
                devices=self.shard.device_ids)
        if self.faults is not None and self.fused_sites:
            self.faults.fire(
                "kernel.launch", batch=self.key.batch,
                resolution=self.key.resolution,
                precision=self.key.precision, sites=self.fused_sites)
        out = self._fn(params, x)
        if self.faults is not None and self._runs_int8:
            out = self.faults.corrupt(
                "epilogue.numerics", out, batch=self.key.batch,
                resolution=self.key.resolution,
                precision=self.key.precision)
        return out

    def warm(self, params) -> "Executor":
        """Trigger compilation (and the first-device-touch costs) on a
        zero batch, outside the request loop."""
        if not self.warmed:
            k = self.key
            x = jnp.zeros((k.batch, k.resolution, k.resolution, 3),
                          jnp.float32)
            jax.block_until_ready(self._fn(params, x))
            self.warmed = True
        return self


class ExecutorCache:
    """LRU cache of ``Executor``s keyed by (batch bucket, resolution).

    ``buckets`` is the ascending set of batch sizes the runtime compiles
    for; ``bucket_for(n)`` picks the smallest bucket >= n (the ragged
    tail of a request group pads only up to that, never to the largest
    microbatch).  The first plan built at a resolution becomes the donor
    for every later bucket at that resolution: their ``plan_program``
    call inherits tuned block choices site-by-site (``reuse=``) instead
    of re-consulting the autotuner.

    ``faults`` / ``neg_ttl_s`` / ``clock`` are the fault-tolerance
    knobs (see the module docstring); all default to inert, so a cache
    built the pre-fault way behaves identically.
    """

    def __init__(self, params, cfg: EfficientViTConfig, *,
                 buckets: Tuple[int, ...] = (1, 2, 4, 8),
                 precision: str = "auto", autotune: bool = True,
                 interpret: bool | None = None,
                 capacity: int | None = None,
                 telemetry: Telemetry | None = None,
                 faults=None, neg_ttl_s: float = 1.0, clock=None,
                 devices=None, artifact=None, tracer=None):
        assert buckets and all(b >= 1 for b in buckets), buckets
        self.params = params
        self.cfg = cfg
        # obs.trace.Tracer (or None): build spans land on the
        # "executors" track; ladder moves and mesh shrinks are recorded
        # as zero-duration marks.  Host clocks only — never a device sync.
        self.tracer = tracer
        if artifact is not None:
            # adopt the searched schedule: validate first (typed
            # ArtifactError on a config-hash/precision mismatch — never
            # silently serve a stale schedule), then take the searched
            # bucket set over the constructor's and seed the tuner
            # cache, so any plan the artifact's overrides don't cover
            # still tunes warm
            artifact.validate_for(cfg, precision)
            buckets = artifact.buckets
            from repro.kernels.autotune import import_entries
            import_entries(artifact.tuner_cache)
        self.artifact = artifact
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.precision = precision
        self.autotune = autotune
        self.interpret = interpret
        self.capacity = capacity
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.faults = faults
        self.neg_ttl_s = float(neg_ttl_s)
        self.clock = clock if clock is not None else time.monotonic
        # devices=None -> classic single-device jit on the default
        # device; a device list (even of one) -> every executor is a
        # batch-sharded shard_map over the survivors in DeviceHealth
        self.health = DeviceHealth.of(devices) if devices is not None \
            else None
        if self.health is not None:
            self.health.tracer = tracer
        self._lru: "collections.OrderedDict[ExecutorKey, Executor]" = \
            collections.OrderedDict()
        self._donor_plans: dict[int, object] = {}   # resolution -> plan
        self._neg: dict[ExecutorKey, tuple[float, ReproError]] = {}
        self._degrade: dict[ExecutorKey, DegradeState] = {}

    # -- bucket policy ---------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n; the largest bucket when n exceeds all
        (the caller then splits n across several dispatches)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    # -- the cache -------------------------------------------------------
    def _key(self, batch: int, resolution: int) -> ExecutorKey:
        return ExecutorKey(int(batch), int(resolution), self.precision)

    def get(self, batch: int, resolution: int) -> Executor:
        key = self._key(batch, resolution)
        ex = self._lru.get(key)
        if ex is not None:
            self._lru.move_to_end(key)
            self.telemetry.count("executor_hit")
            return ex
        neg = self._neg.get(key)
        if neg is not None:
            expiry, cause = neg
            if self.clock() < expiry:
                # hot failing bucket: answer from the negative cache
                # instead of re-running the whole compile pipeline
                self.telemetry.count("negative_cache_hit")
                err = ExecutorError(
                    f"executor {key} failed recently (negative-cached "
                    f"for {self.neg_ttl_s:g}s): {cause}", key=key,
                    site=getattr(cause, "site", None))
                raise err from cause
            del self._neg[key]
        self.telemetry.count("executor_miss")
        bspan = None
        if self.tracer is not None:
            bspan = self.tracer.begin(
                "executor.build", track="executors", bucket=key.batch,
                resolution=key.resolution, precision=key.precision)
        try:
            ex = self._build(key, parent=bspan)
        except MeshExhausted as e:
            # no compile ran and no device will come back — keep the
            # typed error un-wrapped and un-cached so every caller sees
            # MeshExhausted itself, not a negative-cache ExecutorError
            self.telemetry.count("executor_build_failed")
            self._t_end(bspan, error=type(e).__name__)
            raise
        except ReproError as e:
            self._note_build_failure(key, e)
            self._t_end(bspan, error=type(e).__name__)
            raise
        except Exception as e:  # non-typed crash inside lower/plan/jit
            err = ExecutorError(f"executor build failed for {key}: {e}",
                                key=key)
            self._note_build_failure(key, err)
            self._t_end(bspan, error=type(e).__name__)
            raise err from e
        self._t_end(bspan, fused_sites=len(ex.fused_sites),
                    degraded=ex.degraded is not None
                    and ex.degraded.degraded)
        self._lru[key] = ex
        while self.capacity is not None and len(self._lru) > self.capacity:
            evicted_key, _ = self._lru.popitem(last=False)
            self.telemetry.count("executor_evicted")
            if not any(k.resolution == evicted_key.resolution
                       for k in self._lru):
                self._donor_plans.pop(evicted_key.resolution, None)
        return ex

    def executor_for(self, n: int, resolution: int) -> Executor:
        """The executor serving a group of ``n`` same-resolution
        requests: smallest cached bucket >= n."""
        return self.get(self.bucket_for(n), resolution)

    # -- tracing helpers (no-ops without a tracer) -----------------------
    def _t_end(self, span, **attrs) -> None:
        if self.tracer is not None and span is not None:
            self.tracer.end(span, **attrs)

    def _t_mark(self, name: str, **attrs) -> None:
        """Zero-duration mark on the executors track (ladder moves,
        mesh shrinks) — a begin/end pair at one clock reading."""
        if self.tracer is not None:
            self.tracer.end(self.tracer.begin(name, track="executors",
                                              **attrs))

    def _note_build_failure(self, key: ExecutorKey,
                            err: ReproError) -> None:
        """Record a failed build: count it and negative-cache the key.

        Nothing was inserted into the LRU (insertion happens only after
        a successful build) and the donor plan is only published on
        success, so there is no half-built state to roll back — only
        the short-TTL negative entry to write.
        """
        self.telemetry.count("executor_build_failed")
        if self.neg_ttl_s > 0:
            self._neg[key] = (self.clock() + self.neg_ttl_s, err)

    def _build(self, key: ExecutorKey, parent=None) -> Executor:
        # pick the device slice first: an exhausted mesh must raise its
        # typed error before any compile work (or compile fault) runs
        shard = self.health.shard_for(key.batch) \
            if self.health is not None else None
        if self.faults is not None:
            self.faults.fire("executor.compile", batch=key.batch,
                             resolution=key.resolution,
                             precision=key.precision)
        state = self._degrade.get(key)
        lspan = None
        if self.tracer is not None:
            lspan = self.tracer.begin("lower", parent=parent)
        # sharded executors lower/plan at the LOCAL batch — shard_map
        # hands each device its own slice of the bucket
        program = lower(self.cfg,
                        batch=shard.local_batch if shard is not None
                        else key.batch,
                        image_size=key.resolution)
        self._t_end(lspan)
        plan = None
        if not (state is not None and state.level >= 2):
            precision = "fp" if (state is not None and state.pinned_fp) \
                else self.precision
            donor = self._donor_plans.get(key.resolution)
            # artifact-pinned schedule: overrides reproduce the searched
            # plan with zero tuner consultation; an uncovered shape
            # (e.g. a sharded executor's local batch) gets None and
            # plans normally.  A degraded key plans WITHOUT the
            # artifact — its demote= ladder must win over the pins.
            overrides = None
            if self.artifact is not None \
                    and (state is None or not state.degraded):
                overrides = self.artifact.overrides_for(
                    shard.local_batch if shard is not None else key.batch,
                    key.resolution)
            pspan = None
            if self.tracer is not None:
                pspan = self.tracer.begin("plan", parent=parent,
                                          reused_donor=donor is not None)
            plan = plan_program(program, self.params,
                                autotune=self.autotune,
                                interpret=self.interpret,
                                precision=precision, reuse=donor,
                                demote=(state.demoted if state is not None
                                        else ()),
                                overrides=overrides)
            routes = _plan_routes(plan)
            for name, n in routes["counters"].items():
                self.telemetry.count(name, n)
            self._t_end(pspan, **routes["attrs"])
            self.telemetry.count("plans_built")
            reused = sum(d.reused for d in plan.decisions.values())
            if reused:
                self.telemetry.count("plan_sites_reused", reused)
            # degraded plans never become donors: their demotions and
            # forced precision must not leak into healthy buckets
            if donor is None and (state is None or not state.degraded):
                self._donor_plans[key.resolution] = plan
        fn = sharded_forward(program, self.params, plan=plan,
                             shard=shard) if shard is not None else None
        return Executor(key, program, plan, faults=self.faults,
                        degraded=state, fn=fn, shard=shard)

    # -- per-device fault domains ----------------------------------------
    @property
    def mesh_exhausted(self) -> bool:
        """True when a device mesh is configured and fully dead."""
        return self.health is not None and self.health.exhausted

    def on_device_lost(self, device_id: int | None) -> bool:
        """Shrink the mesh around a dead device.

        Marks the device dead in the health registry, evicts every
        cached executor whose shard included it (the next ``get``
        replans on the survivors at the new local batch) and clears the
        negative cache — its entries may record failures the dead
        device caused.  Donor plans survive: block choices are
        shape-keyed and site-by-site reuse already spans batch sizes.
        Returns True when the mesh actually shrank (newly-dead device).
        """
        if self.health is None or device_id is None:
            return False
        if not self.health.mark_dead(device_id):
            return False
        self.telemetry.count("device_lost")
        self.telemetry.record_device_error(device_id, lost=True)
        self._t_mark("mesh.shrink", device=device_id,
                     alive=self.health.n_alive, epoch=self.health.epoch)
        stale = [k for k, ex in self._lru.items()
                 if ex.shard is not None and device_id in ex.device_ids]
        for k in stale:
            del self._lru[k]
        self._neg.clear()
        if not self.health.exhausted:
            self.telemetry.count("mesh_shrunk")
        return True

    # -- the degradation ladder ------------------------------------------
    def degradation(self, batch: int, resolution: int
                    ) -> Optional[DegradeState]:
        """The key's ladder state (None = healthy, never degraded)."""
        return self._degrade.get(self._key(batch, resolution))

    def _apply_degrade(self, key: ExecutorKey, state: DegradeState,
                       counter: str) -> DegradeState:
        self._degrade[key] = state
        # evict the current executor (and any negative entry) so the
        # next get() rebuilds at the new ladder level immediately
        self._lru.pop(key, None)
        self._neg.pop(key, None)
        self.telemetry.count(counter)
        return state

    def degrade(self, batch: int, resolution: int, *,
                site: str | None = None) -> DegradeState:
        """Move one key down the ladder after a fused-launch / compile
        failure: demote the blamed ``site`` first (everything else
        stays fused); with no site to blame — or when the demoted plan
        failed too — fall to the reference IR interpreter."""
        key = self._key(batch, resolution)
        state = self._degrade.get(key, DegradeState())
        if site is not None and state.level == 0:
            state = dataclasses.replace(
                state, level=1, demoted=state.demoted | {site})
        elif site is not None and state.level == 1 \
                and site not in state.demoted:
            state = dataclasses.replace(
                state, demoted=state.demoted | {site})
        else:
            state = dataclasses.replace(state, level=2)
        self._t_mark("ladder.degrade", bucket=key.batch,
                     resolution=key.resolution, site=site,
                     level=state.level, demoted=sorted(state.demoted))
        return self._apply_degrade(key, state, "degraded")

    def pin_fp(self, batch: int, resolution: int) -> DegradeState:
        """Pin one key's plan to forced-fp precision (degraded-mode
        flag) — the response to detected int8 NaN/overflow: on a
        quantized tree every int8 kernel demotes to the reference path,
        so correctness survives while the key stays compiled."""
        key = self._key(batch, resolution)
        state = dataclasses.replace(
            self._degrade.get(key, DegradeState()), pinned_fp=True)
        self._t_mark("ladder.pin_fp", bucket=key.batch,
                     resolution=key.resolution, level=state.level)
        return self._apply_degrade(key, state, "pinned_fp")

    # -- introspection / lifecycle --------------------------------------
    def keys(self) -> Tuple[ExecutorKey, ...]:
        """Currently cached keys, least- to most-recently used."""
        return tuple(self._lru)

    def __len__(self) -> int:
        return len(self._lru)

    def warmup(self, resolutions, buckets=None) -> "ExecutorCache":
        """Pre-build and compile the expected working set (every (bucket,
        resolution) pair) before traffic arrives, so no request pays a
        lowering/planning/compile stall.  An entry whose warm-time
        compile crashes is evicted (no half-built executor stays cached)
        before the error propagates."""
        for res in resolutions:
            for b in (buckets if buckets is not None else self.buckets):
                ex = self.get(b, res)
                try:
                    ex.warm(self.params)
                except Exception:
                    self._lru.pop(ex.key, None)
                    self.telemetry.count("executor_build_failed")
                    raise
        return self
