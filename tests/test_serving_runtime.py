"""Serving runtime: executor cache, micro-batching scheduler, telemetry,
multi-resolution lowering, and the autotune cache-key audit.

The contracts under test:
  * ``lower`` is resolution/batch-parameterized with geometry validated
    at lowering time, and ``execute`` over any (batch, resolution) pair
    agrees with the reference forward in both precisions;
  * ``ExecutorCache`` compiles lazily, serves LRU, evicts at capacity,
    and shares fusion-plan block choices across batch buckets at the
    same resolution (``plan_program(..., reuse=)``);
  * the scheduler groups same-resolution requests into the largest
    ready bucket, routes ragged tails to the smallest covering bucket
    (zero pad waste when the tail IS a bucket), and flushes on deadline;
  * autotune persistent-cache keys carry batch + spatial dims, so
    bucketed shapes cannot collide on stale block choices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.efficientvit import B1, B1_SMOKE, efficientvit, init_efficientvit
from repro.core.fusion import plan_program
from repro.core.program import execute, lower
from repro.core.quantization import quantize_efficientvit
from repro.serving.executors import ExecutorCache, ExecutorKey
from repro.serving.scheduler import (
    ManualClock, MicroBatchScheduler, Request, form_batches)
from repro.serving.telemetry import Telemetry, percentile


@pytest.fixture
def smoke_params():
    return init_efficientvit(jax.random.PRNGKey(0), B1_SMOKE)


def _images(n, res, seed=1):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (n, res, res, 3)), np.float32)


# ---------------------------------------------------------------------------
# multi-resolution lowering + execute parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("res", [192, 224, 256])
@pytest.mark.parametrize("batch", [1, 4, 8])
def test_lower_multi_resolution_geometry(res, batch):
    """B1 lowers at serving resolutions/batches with a consistent shape
    chain (validated inside lower) and the expected head geometry."""
    program = lower(B1, batch=batch, image_size=res)
    assert program.batch == batch and program.image_size == res
    r = res // 32
    gap = program.site("head.gap")
    assert gap.in_shape == (batch, r, r, B1.head_widths[0])
    assert program.sites[-1].out_shape == (batch, B1.num_classes)
    # every site consumes its predecessor's output (chain re-check)
    for prev, cur in zip(program.sites, program.sites[1:]):
        assert cur.in_shape == prev.out_shape, (prev.name, cur.name)


def test_lower_rejects_bad_geometry():
    with pytest.raises(ValueError, match="multiples of 32"):
        lower(B1, image_size=200)
    with pytest.raises(ValueError, match="batch"):
        lower(B1, batch=0)


@pytest.mark.parametrize("res,batch", [(32, 1), (32, 4), (64, 2), (96, 1)])
def test_multi_resolution_reference_is_the_forward(smoke_params, res, batch):
    """plan=None execute == the efficientvit shim, bit-for-bit, at every
    (resolution, batch) pair."""
    x = _images(batch, res)
    program = lower(B1_SMOKE, batch=batch, image_size=res)
    ref = execute(program, smoke_params, x)
    shim = efficientvit(smoke_params, x, B1_SMOKE)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(shim))


@pytest.mark.parametrize("res,batch", [(32, 4), (64, 2), (96, 1)])
def test_multi_resolution_fused_parity_fp(smoke_params, res, batch,
                                          tmp_autotune_cache):
    x = _images(batch, res)
    program = lower(B1_SMOKE, batch=batch, image_size=res)
    plan = plan_program(program, smoke_params, autotune=False)
    ref = execute(program, smoke_params, x)
    fus = execute(program, smoke_params, x, plan=plan)
    assert_allclose(np.asarray(fus), np.asarray(ref), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("res,batch", [(32, 1), (64, 1), (32, 2)])
def test_multi_resolution_fused_parity_int8(smoke_params, res, batch,
                                            tmp_autotune_cache, int8_parity):
    """Batch 1: int8-fused emits the int8 reference chain's codes bit
    for bit at every boundary (the in-kernel requant decisions
    coincide), its fp32 tail within the ulps ``int8_parity`` argues;
    batch > 1 within quantization noise with the top-1 label
    preserved."""
    qparams = quantize_efficientvit(smoke_params)
    x = _images(batch, res)
    program = lower(B1_SMOKE, batch=batch, image_size=res)
    plan = plan_program(program, qparams, autotune=False)
    assert all(d.precision == "int8"
               for d in plan.decisions.values() if d.fused)
    if batch == 1:
        int8_parity(program, qparams, x, plan)
    else:
        ref = execute(program, qparams, x)
        fus = execute(program, qparams, x, plan=plan)
        assert bool((jnp.argmax(ref, -1) == jnp.argmax(fus, -1)).all())
        assert float(jnp.max(jnp.abs(ref - fus))) < 1e-2


def test_plan_vmem_fallback_at_large_resolution(tmp_autotune_cache):
    """B1 @384 fp: the early high-resolution MBConvs used to blow the
    8 MB VMEM budget and demote to the reference path with reason
    "vmem".  Spatially-banded super-sites retire that fallback — the
    grouping pass rescues the demoted S1 pair with a row-banded group,
    so the 384 plan demotes NOTHING in either precision, and the fused
    forward still matches the reference at 384.  @256 nothing falls
    back either."""
    params = init_efficientvit(jax.random.PRNGKey(5), B1)
    qparams = quantize_efficientvit(params)

    p384 = lower(B1, batch=1, image_size=384)
    fp_plan = plan_program(p384, params, autotune=False)
    vmem_sites = {d.name for d in fp_plan.decisions.values()
                  if d.reason == "vmem"}
    assert vmem_sites == set(), vmem_sites
    assert all(d.fused for d in fp_plan.decisions.values())
    # the rescue is a banded super-site over the former demotion pair
    assert any(set(g.members) == {"S1.mb0", "S1.mb1"}
               and g.blocks.get("block_rows")
               for g in fp_plan.groups.values()), fp_plan.groups
    q_plan = plan_program(p384, qparams, autotune=False)
    assert not any(d.reason == "vmem" for d in q_plan.decisions.values())

    # fused parity at the rescued resolution (the banding is exact: the
    # band boundary only splits rows the 1x1 stages treat pointwise)
    x384 = _images(1, 384)
    ref = execute(p384, params, x384)
    fus = execute(p384, params, x384, plan=fp_plan)
    assert_allclose(np.asarray(fus), np.asarray(ref), rtol=1e-3, atol=1e-3)

    p256 = lower(B1, batch=1, image_size=256)
    for tree in (params, qparams):
        plan = plan_program(p256, tree, autotune=False)
        assert all(d.fused for d in plan.decisions.values()), \
            {d.name: d.reason for d in plan.decisions.values() if not d.fused}


# ---------------------------------------------------------------------------
# executor cache
# ---------------------------------------------------------------------------

def test_executor_cache_hit_miss_eviction(smoke_params, tmp_autotune_cache):
    cache = ExecutorCache(smoke_params, B1_SMOKE, buckets=(1, 2),
                          autotune=False, capacity=2)
    a = cache.get(1, 64)
    assert cache.get(1, 64) is a                      # hit
    cache.get(2, 64)
    assert cache.telemetry.counters["executor_miss"] == 2
    assert cache.telemetry.counters["executor_hit"] == 1
    cache.get(1, 32)                                  # evicts LRU (1, 64)
    assert cache.telemetry.counters["executor_evicted"] == 1
    assert ExecutorKey(1, 64, "auto") not in cache.keys()
    assert len(cache) == 2
    b = cache.get(1, 64)                              # rebuilt, not the
    assert b is not a                                 # evicted object


def test_executor_cache_plan_reuse_across_buckets(smoke_params,
                                                  tmp_autotune_cache):
    """The first plan at a resolution donates its tuned blocks to every
    later bucket at that resolution; another resolution tunes fresh."""
    cache = ExecutorCache(smoke_params, B1_SMOKE, buckets=(1, 2, 4),
                          autotune=False)
    donor = cache.get(4, 64)
    assert not any(d.reused for d in donor.plan.decisions.values())
    ex1 = cache.get(1, 64)
    fused = [d for d in ex1.plan.decisions.values() if d.fused]
    assert fused and all(d.reused for d in fused)
    for name, d in ex1.plan.decisions.items():
        if d.fused:
            assert d.blocks == donor.plan.decisions[name].blocks
    assert cache.telemetry.counters["plan_sites_reused"] == len(fused)
    other = cache.get(1, 32)                          # new resolution:
    assert not any(d.reused for d in other.plan.decisions.values())


def test_bucket_cover(smoke_params):
    cache = ExecutorCache(smoke_params, B1_SMOKE, buckets=(1, 2, 4))
    assert cache.bucket_for(1) == 1 and cache.bucket_for(3) == 4
    assert cache.bucket_for(9) == 4          # caller splits
    # VisionEngine.logits chunks a batch as a due queue
    def chunks(n):
        return form_batches(n, cache.buckets, due=True)
    assert chunks(7) == [4, 4]               # tail 3 -> smallest bucket >= 3
    assert chunks(5) == [4, 1]
    assert chunks(4) == [4]
    assert chunks(3) == [4]                  # 3 pads into one 4-bucket


def test_executor_warmup_compiles_working_set(smoke_params,
                                              tmp_autotune_cache):
    cache = ExecutorCache(smoke_params, B1_SMOKE, buckets=(1, 2),
                          autotune=False)
    cache.warmup((64,))
    assert {(k.batch, k.resolution) for k in cache.keys()} == \
        {(1, 64), (2, 64)}
    assert all(cache.get(b, 64).warmed for b in (1, 2))


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _scheduler(params, buckets=(1, 2, 4), clock=None, precision="auto"):
    cache = ExecutorCache(params, B1_SMOKE, buckets=buckets,
                          precision=precision, autotune=False)
    return MicroBatchScheduler(cache, params, clock=clock)


def test_scheduler_bucketed_tail_no_padding(smoke_params,
                                            tmp_autotune_cache):
    """5 same-resolution requests over buckets (1,2,4) dispatch as a
    full 4-bucket plus a 1-bucket tail — zero padded slots — and match
    the reference forward."""
    sched = _scheduler(smoke_params)
    imgs = _images(5, 32)
    out = sched.serve([Request(rid=i, image=imgs[i]) for i in range(5)])
    tel = sched.telemetry
    assert tel.total("padded") == 0 and tel.total("samples") == 5
    assert {key[0] for key in tel.buckets} == {1, 4}
    ref = efficientvit(smoke_params, imgs, B1_SMOKE)
    assert_allclose(out, np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_scheduler_only_dispatches_full_buckets_until_due(smoke_params,
                                                          tmp_autotune_cache):
    clock = ManualClock()
    sched = _scheduler(smoke_params, clock=clock)
    imgs = _images(5, 32)
    for i in range(5):
        sched.submit(Request(rid=i, image=imgs[i]))
    assert sched.step() == 4                 # one full 4-bucket forms
    assert sched.queue_depth(32) == 1        # tail waits (no deadline)
    assert sched.step() == 0
    assert sched.step(drain=True) == 1       # drain flushes to bucket 1
    sched.finalize()
    assert sched.telemetry.total("padded") == 0


def test_scheduler_deadline_flush(smoke_params, tmp_autotune_cache):
    clock = ManualClock()
    sched = _scheduler(smoke_params, clock=clock)
    sched.submit(Request(rid=0, image=_images(1, 32)[0], deadline_ms=10.0))
    assert sched.step() == 0                 # not due, bucket not full
    clock.advance(0.02)
    assert sched.step() == 1                 # deadline flushes the tail
    sched.finalize()
    (key,) = sched.telemetry.buckets
    assert key[0] == 1                       # smallest covering bucket


def test_scheduler_mixed_resolutions(smoke_params, tmp_autotune_cache):
    """Queues are per-resolution; logits come back in request order and
    match each resolution's reference forward."""
    sched = _scheduler(smoke_params, buckets=(1, 2))
    img32, img64 = _images(3, 32), _images(2, 64, seed=2)
    reqs = [Request(rid=0, image=img32[0]), Request(rid=1, image=img64[0]),
            Request(rid=2, image=img32[1]), Request(rid=3, image=img64[1]),
            Request(rid=4, image=img32[2])]
    out = sched.serve(reqs)
    assert out.shape == (5, B1_SMOKE.num_classes)
    ref32 = np.asarray(efficientvit(smoke_params, img32, B1_SMOKE))
    ref64 = np.asarray(efficientvit(smoke_params, img64, B1_SMOKE))
    assert_allclose(out[[0, 2, 4]], ref32, rtol=1e-3, atol=1e-3)
    assert_allclose(out[[1, 3]], ref64, rtol=1e-3, atol=1e-3)


def test_bucketed_policy_formation():
    buckets = (1, 2, 4)
    assert form_batches(9, buckets, due=False) == [4, 4]
    assert form_batches(9, buckets, due=True) == [4, 4, 1]
    assert form_batches(3, buckets, due=False) == []
    assert form_batches(3, buckets, due=True) == [4]


# ---------------------------------------------------------------------------
# VisionEngine façade
# ---------------------------------------------------------------------------

def test_vision_engine_tail_routes_to_small_bucket(smoke_params,
                                                   tmp_autotune_cache):
    from repro.serving.vision import VisionEngine, VisionServeConfig
    eng = VisionEngine(smoke_params, B1_SMOKE,
                       VisionServeConfig(microbatch=4, autotune=False))
    imgs = _images(5, 64)
    logits = eng.logits(imgs)
    ref = efficientvit(smoke_params, imgs, B1_SMOKE)
    assert_allclose(np.asarray(logits), np.asarray(ref),
                    rtol=1e-3, atol=1e-3)
    used = {(k.batch, k.resolution) for k in eng.cache.keys()}
    assert (1, 64) in used                     # tail bucket, not pad-to-4
    assert eng.telemetry.total("padded") == 0


def test_vision_engine_quantized_serve(smoke_params, tmp_autotune_cache):
    """FIX8 serving through the scheduler: 3 requests over buckets (1,2)
    dispatch 2+1 and match the reference computed with the same
    chunking (dynamic act scales are per-dispatch)."""
    from repro.serving.vision import VisionEngine, VisionServeConfig
    eng = VisionEngine.quantized(
        smoke_params, B1_SMOKE,
        VisionServeConfig(microbatch=2, autotune=False))
    imgs = _images(3, 64)
    out = eng.serve([Request(rid=i, image=imgs[i]) for i in range(3)])
    ref = np.concatenate([
        np.asarray(efficientvit(eng.params, imgs[:2], B1_SMOKE)),
        np.asarray(efficientvit(eng.params, imgs[2:], B1_SMOKE))])
    # batch-1 chunk is bit-exact; the batch-2 chunk is within
    # quantization noise (in-kernel requant vs the reference chain)
    np.testing.assert_array_equal(out[2], ref[2])
    assert float(np.max(np.abs(out - ref))) < 1e-2
    assert bool((out.argmax(-1) == ref.argmax(-1)).all())
    assert all(k.precision == "int8" for k in eng.cache.keys())


# ---------------------------------------------------------------------------
# autotune cache-key audit (regression for bucket collisions)
# ---------------------------------------------------------------------------

def test_shape_key_carries_batch_and_spatial():
    from repro.kernels.autotune import shape_key
    base = dict(c=16, f=32, dtype="f32", backend="interp")
    k1 = shape_key(batch=1, spatial=(64, 64), **base)
    k2 = shape_key(batch=8, spatial=(64, 64), **base)
    k3 = shape_key(batch=1, spatial=(96, 96), **base)
    assert len({k1, k2, k3}) == 3
    assert "b=1" in k1 and "s=64x64" in k1
    assert "b=8" in k2 and "s=96x96" in k3
    # scalar spatial (token counts) normalizes
    assert "s=49" in shape_key(batch=4, spatial=49, d=16, dtype="f32",
                               backend="interp")


@pytest.mark.parametrize("kind", ["mbconv", "dsconv", "relu_attn"])
def test_tuner_keys_distinct_across_buckets(kind, monkeypatch,
                                            tmp_autotune_cache):
    """Every kernel family's tuner must key its persistent cache on
    batch AND spatial dims: two serving buckets differing only there
    may never share (or overwrite) a block choice."""
    captured = []

    def fake_autotune(k, key, candidates, bench=None, *, interpret=True):
        captured.append((k, tuple(key)))
        return dict(candidates[0])

    if kind == "mbconv":
        from repro.kernels.mbconv import ops
        monkeypatch.setattr(ops, "autotune", fake_autotune)
        ops.tune_block_f((1, 64, 64, 8), 32, 16, allow_sweep=False)
        ops.tune_block_f((8, 64, 64, 8), 32, 16, allow_sweep=False)
        ops.tune_block_f((1, 96, 96, 8), 32, 16, allow_sweep=False)
    elif kind == "dsconv":
        from repro.kernels.dsconv import ops
        monkeypatch.setattr(ops, "autotune", fake_autotune)
        ops.tune_block_f((1, 64, 64, 8), 16, allow_sweep=False)
        ops.tune_block_f((8, 64, 64, 8), 16, allow_sweep=False)
        ops.tune_block_f((1, 96, 96, 8), 16, allow_sweep=False)
    else:
        from repro.kernels.relu_attn import ops
        monkeypatch.setattr(ops, "autotune", fake_autotune)
        ops.tune_block_n(2, 256, 16, allow_sweep=False)    # batch bucket 1
        ops.tune_block_n(16, 256, 16, allow_sweep=False)   # batch bucket 8
        ops.tune_block_n(2, 576, 16, allow_sweep=False)    # other resolution
    keys = [key for _, key in captured]
    assert len(set(keys)) == 3, keys
    for key in keys:
        assert any(p.startswith("b=") for p in key), key
        assert any(p.startswith("s=") for p in key), key


def test_dsconv_tune_reads_persistent_cache(tmp_autotune_cache):
    """dsconv now tunes for real: a seeded cache entry under the new
    batch+spatial key is honored instead of the old hardcoded 128."""
    from repro.kernels import autotune as at
    from repro.kernels.dsconv.ops import tune_block_f
    key = at.shape_key(batch=2, spatial=(64, 64), c=8, f=8, stride=1,
                       dtype="f32", backend="interp")
    at._MEM[at._key("dsconv", key)] = {"block_f": 256}
    assert tune_block_f((2, 64, 64, 8), 8, allow_sweep=False,
                        interpret=True) == 256
    # a different batch bucket misses that entry -> heuristic first
    # candidate (128), NOT the batch-2 choice: no cross-bucket collision
    assert tune_block_f((4, 64, 64, 8), 8, allow_sweep=False,
                        interpret=True) == 128
    at.clear_memory_cache()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_telemetry_bucket_math_and_table():
    tel = Telemetry()
    key = (4, 224, "fp")
    tel.record_dispatch(key, 4, 4, queue_depth=2, wait_ms=[1.0, 2.0])
    tel.record_dispatch(key, 1, 4, queue_depth=0, wait_ms=[8.0])
    tel.record_latency(key, [5.0, 6.0])
    b = tel.bucket(key)
    assert b.dispatches == 2 and b.samples == 5 and b.padded == 3
    assert b.occupancy == pytest.approx(5 / 8)
    snap = tel.snapshot()
    assert snap["padded_total"] == 3 and snap["samples_total"] == 5
    assert snap["buckets"]["4/224/fp"]["wait_ms_p50"] == 2.0
    table = tel.table()
    assert "4x224xfp" in table and "TOTAL" in table
    assert percentile([], 0.5) != percentile([], 0.5)  # nan on empty
    assert percentile([1.0, 3.0], 0.5) == 2.0


def test_telemetry_counters_and_series():
    tel = Telemetry()
    tel.count("x")
    tel.count("x", 2)
    tel.observe("occ", 0.5)
    tel.observe("occ", 1.0)
    snap = tel.snapshot()
    assert snap["counters"]["x"] == 3
    assert snap["series"]["occ"]["n"] == 2
    assert snap["occupancy"] == 1.0            # no buckets yet
