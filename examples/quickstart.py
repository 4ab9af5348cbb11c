"""Quickstart: the paper's model + technique in five minutes on CPU.

1. Build EfficientViT-B1 (smoke size) and run an image through it.
2. Build a fusion plan and run the whole network through the fused
   Pallas megakernels (MBConv + single-pass MSA) — check they agree.
3. Quantize the network to FIX8 (the paper's datapath) and compare.
4. Ask the cycle-level accelerator model for the paper's Table II row.
5. Use the paper's attention as an LM backend and decode with O(1) state.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_arch, smoke_variant
from repro.core.accelerator_model import analyze_program
from repro.core.efficientvit import B1, B1_SMOKE, efficientvit, init_efficientvit
from repro.core.fusion import launch_counts, plan_program
from repro.core.program import execute, lower
from repro.core.quantization import quantization_error, quantize_efficientvit
from repro.models.registry import build_model

key = jax.random.PRNGKey(0)

# -- 1. EfficientViT forward ------------------------------------------------
params = init_efficientvit(key, B1_SMOKE)
img = jax.random.normal(key, (1, 64, 64, 3))
logits = jax.jit(lambda p, x: efficientvit(p, x, B1_SMOKE))(params, img)
print(f"[1] EfficientViT-B1(smoke) logits: {logits.shape}, "
      f"top-1 class {int(jnp.argmax(logits))}")

# -- 2. fused inference path (TMP dataflow on TPU) ---------------------------
program = lower(B1_SMOKE, batch=1)
plan = plan_program(program, params, autotune=False)
logits_kernel = jax.jit(
    lambda p, x: execute(program, p, x, plan=plan))(params, img)
err = float(jnp.max(jnp.abs(logits - logits_kernel)))
lc = launch_counts(plan)
print(f"[2] fused plan: {plan.n_fused()}/{len(plan.decisions)} sites fused, "
      f"{lc['reference']} -> {lc['fused']} kernel launches, "
      f"max|Δ| vs reference: {err:.2e}")

# -- 3. FIX8 quantization (paper §IV-A) --------------------------------------
qparams = quantize_efficientvit(params)
qlogits = jax.jit(lambda p, x: efficientvit(p, x, B1_SMOKE))(qparams, img)
print(f"[3] FIX8 relative L2 error: {float(quantization_error(logits, qlogits)):.4f}")

# -- 4. the accelerator the paper built --------------------------------------
rep, stages, _ = analyze_program(lower(B1))
print(f"[4] cycle model @B1/224px: {rep.gops:.1f} GOPS "
      f"(paper 780.2), util {rep.utilization:.1%} (paper >95%), "
      f"{rep.gops_per_w:.1f} GOPS/W (paper 105.1)")

# -- 5. the technique as an LM attention backend ------------------------------
arch = smoke_variant(get_arch("stablelm-12b")).scaled(
    attn_backend="relu_linear")
model = build_model(arch)
lm_params = model.init(key)
caches = model.init_caches(1, 64)
tok = jnp.zeros((1, 1), jnp.int32)
for pos in range(4):
    lg, caches = jax.jit(model.decode)(lm_params, caches, tok, jnp.int32(pos))
    tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(caches))
print(f"[5] relu_linear LM decode: 4 tokens generated; persistent state "
      f"{state_bytes / 1024:.0f} KiB — O(1) in context length "
      f"(a softmax KV cache grows linearly)")
print("quickstart OK")
