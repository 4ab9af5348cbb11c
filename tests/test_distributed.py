"""Distributed behaviour on 8 fake CPU devices (subprocess-isolated so the
fake-device XLA flag never leaks into other tests).

Covers: partition-rule resolution, sharded train step == single-device
step (SPMD correctness), ZeRO state sharding, elastic reshard, checkpoint
restore onto a different mesh, compressed cross-pod psum.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str) -> dict:
    """Run ``body`` in a subprocess with 8 fake devices; returns its JSON."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_partition_rules_resolution():
    r = run_sub("""
        from repro.distributed.partition import make_ctx, resolve_param_spec
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ctx = make_ctx(mesh)
        # dividing dims pick up (fsdp, tp)
        s1 = resolve_param_spec(ctx, ("fsdp", "tp"), (8, 16))
        # non-dividing expert dim releases its axis; d_ff claims it
        s2 = resolve_param_spec(ctx, ("ep", "fsdp", "tp"), (3, 8, 16))
        # leading stack dims stay unsharded (right-alignment)
        s3 = resolve_param_spec(ctx, ("fsdp", "tp"), (7, 8, 16))
        print(json.dumps({"s1": str(s1), "s2": str(s2), "s3": str(s3)}))
    """)
    assert r["s1"] == "PartitionSpec('data', 'model')"
    assert r["s2"] == "PartitionSpec(None, 'data', 'model')"
    assert r["s3"] == "PartitionSpec(None, 'data', 'model')"


def test_sharded_train_step_matches_single_device():
    """One sharded train step == the same step computed unsharded."""
    r = run_sub("""
        from repro.configs import get_arch, smoke_variant
        from repro.distributed.ctx import use_sharding
        from repro.distributed.partition import (
            make_ctx, match_partition_rules, named_shardings)
        from repro.distributed.rules import LM_RULES
        from repro.launch.steps import (
            default_opt_cfg, init_train_state, make_train_step)
        from repro.models.registry import build_model

        cfg = smoke_variant(get_arch("granite-3-2b"))
        model = build_model(cfg)
        opt_cfg = default_opt_cfg(cfg)
        params, opt = init_train_state(model, opt_cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "targets": jnp.ones((8, 32), jnp.int32)}
        step = make_train_step(model, opt_cfg)

        # single-device reference
        p1, o1, l1 = jax.jit(step)(params, opt, batch)

        # sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ctx = make_ctx(mesh)
        specs = match_partition_rules(LM_RULES, params, ctx)
        shardings = named_shardings(specs, mesh)
        params_s = jax.tree.map(jax.device_put, params, shardings)
        batch_s = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        with use_sharding(ctx), mesh:
            p2, o2, l2 = jax.jit(step)(params_s, opt, batch_s)
        diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                   for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        # params are actually sharded across devices
        n_shards = len(jax.tree.leaves(p2)[0].sharding.device_set)
        print(json.dumps({"l1": float(l1), "l2": float(l2), "pdiff": diff,
                          "n_shards": n_shards}))
    """)
    assert abs(r["l1"] - r["l2"]) < 2e-3, r
    assert r["pdiff"] < 2e-3, r
    assert r["n_shards"] > 1


def test_elastic_reshard_and_ckpt_cross_mesh(tmp_path):
    """Save on a (4,2) mesh, restore+reshard onto (2,2) after 'losing' hosts;
    training continues and matches structure."""
    r = run_sub(f"""
        from repro.checkpoint.checkpoint import restore, save
        from repro.configs import get_arch, smoke_variant
        from repro.distributed.partition import (
            make_ctx, match_partition_rules, named_shardings)
        from repro.distributed.rules import LM_RULES
        from repro.models.registry import build_model
        from repro.runtime.elastic import reshard_tree

        cfg = smoke_variant(get_arch("granite-3-2b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))

        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        ctx1 = make_ctx(mesh1)
        params = reshard_tree(params, LM_RULES, ctx1)
        save({str(tmp_path)!r}, 3, params)

        # "lose" 4 hosts -> re-mesh to 4 devices
        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        from jax.sharding import Mesh
        mesh2 = Mesh(devs, ("data", "model"))
        ctx2 = make_ctx(mesh2)
        specs = match_partition_rules(LM_RULES, params, ctx2)
        shardings = named_shardings(specs, mesh2)
        restored, step, _ = restore({str(tmp_path)!r}, params,
                                    shardings=shardings)
        leaf = jax.tree.leaves(restored)[0]
        ok = all(np.allclose(np.asarray(a), np.asarray(b))
                 for a, b in zip(jax.tree.leaves(params),
                                 jax.tree.leaves(restored)))
        print(json.dumps({{"step": step, "ok": bool(ok),
                          "devs": len(leaf.sharding.device_set)}}))
    """)
    assert r["step"] == 3 and r["ok"]
    assert r["devs"] <= 4


def test_compressed_psum_matches_exact():
    r = run_sub("""
        from functools import partial
        from repro.optim.compression import compressed_psum

        mesh = jax.make_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                 out_specs=P("pod"))
        def compressed(x):
            return compressed_psum(x, "pod") * 8.0   # sum, not mean

        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                 out_specs=P("pod"))
        def exact(x):
            return jax.lax.psum(x, "pod")

        a, b = compressed(x), exact(x)
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        print(json.dumps({"rel": rel}))
    """)
    assert r["rel"] < 0.01, r


def test_decode_cache_sharding_resolves():
    """CACHE_RULES produce valid shardings for every arch's cache tree."""
    r = run_sub("""
        from repro.configs import ARCHS, smoke_variant
        from repro.distributed.partition import (
            make_ctx, match_partition_rules)
        from repro.distributed.rules import CACHE_RULES
        from repro.models.registry import build_model

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ctx = make_ctx(mesh)
        counts = {}
        for name, cfg in ARCHS.items():
            sm = smoke_variant(cfg)
            model = build_model(sm)
            caches = jax.eval_shape(lambda m=model: m.init_caches(8, 64))
            specs = match_partition_rules(CACHE_RULES, caches, ctx)
            counts[name] = len(jax.tree.leaves(
                specs, is_leaf=lambda s: hasattr(s, "_normalized_spec")
                or str(type(s).__name__) == "PartitionSpec"))
        print(json.dumps({"n": len(counts),
                          "all_pos": all(v > 0 for v in counts.values())}))
    """)
    assert r["n"] == 10 and r["all_pos"]
