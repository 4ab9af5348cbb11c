"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle, wall-clock on
CPU + analytic VMEM/HBM traffic accounting for the TPU target.

Wall-clock on CPU interpret mode is NOT a TPU number — the meaningful
output is (a) correctness deltas and (b) the bytes-saved accounting that
feeds the EXPERIMENTS.md fusion table (the TPU story: the fused kernel's
intermediate never leaves VMEM).

    PYTHONPATH=src python -m benchmarks.kernel_bench [--json OUT]
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, n=3):
    jax.block_until_ready(fn(*args))    # one warm-up, any output pytree
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n


def bench_relu_attn():
    from repro.kernels.relu_attn.kernel import relu_attn_noncausal
    from repro.kernels.relu_attn.ref import relu_attn_noncausal_ref
    BH, N, D = 8, 1024, 64
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (BH, N, D))
               for i in range(3))
    ref = relu_attn_noncausal_ref(q, k, v)
    out = relu_attn_noncausal(q, k, v, block_n=256)
    err = float(jnp.max(jnp.abs(out - ref)))
    # HBM traffic: unfused = write+read KV state per chunk + Z roundtrip;
    # fused = Q/K/V in once + out once (state lives in VMEM scratch)
    unfused = (3 * BH * N * D + 2 * BH * D * D * (N // 256)
               + 2 * BH * N * D) * 4
    fused = (3 * BH * N * D + BH * N * D) * 4
    print(f"relu_attn  (BH={BH},N={N},D={D}): max|err|={err:.2e}  "
          f"HBM bytes fused/unfused = {fused / 1e6:.1f}/{unfused / 1e6:.1f} MB "
          f"({unfused / fused:.2f}x saved)")
    return err


def bench_dsconv():
    from repro.kernels.dsconv.kernel import dsconv_fused
    from repro.kernels.dsconv.ref import dsconv_ref
    B, HW, C, F = 2, 28, 96, 96
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (B, HW, HW, C))
    dw_w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, C)) * 0.2
    dw_b = jnp.zeros((C,))
    pw_w = jax.random.normal(jax.random.fold_in(key, 2), (C, F)) * 0.2
    pw_b = jnp.zeros((F,))
    out = dsconv_fused(x, dw_w, dw_b, pw_w, pw_b)
    ref = dsconv_ref(x, dw_w, dw_b, pw_w, pw_b)
    err = float(jnp.max(jnp.abs(out - ref)))
    inter = B * HW * HW * C * 4       # the DW output that never hits HBM
    print(f"dsconv     (B={B},{HW}x{HW},C={C}->F={F}): max|err|={err:.2e}  "
          f"intermediate kept in VMEM: {inter / 1e6:.2f} MB/call "
          f"(the paper's aux-buffer fusion)")
    return err


def bench_mbconv():
    from repro.kernels.mbconv.kernel import mbconv_fused
    from repro.kernels.mbconv.ref import mbconv_ref
    B, HW, C, M, F = 2, 16, 32, 128, 32
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (B, HW, HW, C))
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (C, M)) * 0.2
    dw_w = jax.random.normal(jax.random.fold_in(key, 2), (3, 3, M)) * 0.2
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (M, F)) * 0.2
    zm, zf = jnp.zeros((M,)), jnp.zeros((F,))
    out = mbconv_fused(x, w1, zm, dw_w, zm, w2, zf)
    ref = mbconv_ref(x, w1, zm, dw_w, zm, w2, zf)
    err = float(jnp.max(jnp.abs(out - ref)))
    inter = 2 * B * HW * HW * M * 4   # expansion + DW output, VMEM-only
    print(f"mbconv     (B={B},{HW}x{HW},C={C}->M={M}->F={F}): "
          f"max|err|={err:.2e}  intermediates kept in VMEM: "
          f"{inter / 1e6:.2f} MB/call (4x-expanded mid never hits HBM)")
    return err


def bench_mbconv_int8():
    from repro.kernels.mbconv.kernel import mbconv_fused_int8
    from repro.kernels.mbconv.ref import mbconv_int8_ref
    B, HW, C, M, F = 2, 16, 32, 128, 32
    rng = np.random.default_rng(5)
    xq = jnp.asarray(rng.integers(-127, 128, (B, HW, HW, C)), jnp.int8)
    w1 = jnp.asarray(rng.integers(-127, 128, (C, M)), jnp.int8)
    dw = jnp.asarray(rng.integers(-127, 128, (3, 3, M)), jnp.int8)
    w2 = jnp.asarray(rng.integers(-127, 128, (M, F)), jnp.int8)
    s1 = jnp.full((M,), 0.01, jnp.float32)
    sd = jnp.full((M,), 0.01, jnp.float32)
    s2 = jnp.full((F,), 0.01, jnp.float32)
    zm, zf = jnp.zeros((M,)), jnp.zeros((F,))
    args = (xq, jnp.float32(0.02), w1, s1, zm, dw, sd, zm, w2, s2, zf)
    out = mbconv_fused_int8(*args)
    ref = mbconv_int8_ref(*args)
    err = float(jnp.max(jnp.abs(out - ref)))
    inter = 2 * HW * HW * M          # int8 scratches, per batch element
    print(f"mbconv_int8(B={B},{HW}x{HW},C={C}->M={M}->F={F}): "
          f"max|err|={err:.2e}  int8 VMEM scratch: {inter / 1e3:.0f} KB "
          f"(4x less than fp32; mid requantized in-kernel)")
    return err


def bench_int8():
    from repro.kernels.int8_matmul.kernel import int8_matmul
    M, K, N = 512, 512, 512
    key = jax.random.PRNGKey(2)
    xq = jax.random.randint(key, (M, K), -127, 127, jnp.int8)
    wq = jax.random.randint(jax.random.fold_in(key, 1), (K, N), -127, 127,
                            jnp.int8)
    ws = jnp.full((N,), 0.02, jnp.float32)
    out = int8_matmul(xq, wq, 0.05, ws, block_m=128, block_n=128,
                      block_k=128)
    ref = (xq.astype(jnp.int32) @ wq.astype(jnp.int32)).astype(jnp.float32) \
        * 0.05 * ws
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"int8_matmul({M}x{K}x{N}): max|err|={err:.2e}  "
          f"int8 operand bytes = {(M * K + K * N) / 1e6:.2f} MB "
          f"(0.5x of bf16; 2x MXU rate on v5e = the paper's DSP packing)")
    return err


def bench_ssd():
    from repro.kernels.ssd.ops import ssd_op
    from repro.kernels.ssd.ref import ssd_recurrent_ref
    b, s, h, p, g, n = 2, 512, 4, 64, 1, 64
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, s, g, n))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n))
    out = ssd_op(x, dt, A, B, C, chunk=128)
    ref, _ = ssd_recurrent_ref(x, dt, A, B, C)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"ssd        (b={b},s={s},h={h},p={p},n={n}): max|err|={err:.2e}  "
          f"chunked scan: state stays in VMEM across {s // 128} chunks")
    return err


def run(json_out: str | None = None):
    print("# Kernel microbench — Pallas interpret-mode vs jnp oracle")
    benches = (("relu_attn", bench_relu_attn), ("dsconv", bench_dsconv),
               ("mbconv", bench_mbconv), ("mbconv_int8", bench_mbconv_int8),
               ("int8_matmul", bench_int8), ("ssd", bench_ssd))
    errs = {name: fn() for name, fn in benches}
    assert all(e < 1e-2 for e in errs.values()), errs
    if json_out is not None:
        from repro.obs import bench_result, write_result
        doc = bench_result(
            "kernel_bench",
            config=dict(backend=jax.default_backend(), interpret=True),
            metrics=dict(max_err=max(errs.values()), errors=errs),
            gates={f"{name}_err": err < 1e-2
                   for name, err in errs.items()})
        write_result(json_out, doc)
        print(f"ledger written to {json_out}")
    return {"max_err": max(errs.values())}


def main():
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.obs import flag_value
    run(json_out=flag_value(sys.argv[1:], "--json"))


if __name__ == "__main__":
    main()
