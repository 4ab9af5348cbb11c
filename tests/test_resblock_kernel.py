"""The fused ResBlock kernel (``kernels/resblock``) against its fp32
``lax.conv`` oracle, in interpret mode on the CPU: both 3x3 convs with
BN folded, GELU between them, the residual added in-kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.compat import VMEM_LIMIT_BYTES
from repro.kernels.registry import get_kernel
from repro.kernels.resblock.kernel import resblock_fused
from repro.kernels.resblock.ops import resblock_vmem_bytes
from repro.kernels.resblock.ref import resblock_ref


def _args(batch, size, c, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, size, size, c))
    w1 = jax.random.normal(ks[1], (3, 3, c, c)) * (9 * c) ** -0.5
    w2 = jax.random.normal(ks[2], (3, 3, c, c)) * (9 * c) ** -0.5
    b1 = 0.1 * jax.random.normal(ks[3], (c,))
    b2 = 0.1 * jax.random.normal(ks[4], (c,))
    return x, w1, b1, w2, b2


@pytest.mark.parametrize("batch,size,c", [(1, 8, 32), (2, 8, 32),
                                          (1, 16, 32), (2, 16, 32),
                                          (2, 16, 16), (1, 48, 32)])
def test_resblock_kernel_matches_its_oracle(batch, size, c):
    args = _args(batch, size, c, seed=size + c + batch)
    with jax.default_matmul_precision("highest"):
        got = resblock_fused(*args, act="gelu_tanh")
        want = resblock_ref(*args, act="gelu_tanh")
    assert got.shape == (batch, size, size, c)   # 48 rows: three bands
    # 2 x 9 taps x C products, summed per lane group in the banded
    # weights' order vs XLA's conv order: fp32 roundoff of O(1) values
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_vmem_count_admits_l2_and_refuses_what_cannot_fold():
    # L2@224's stem (112x112x32) fits the kind's budget, which leaves
    # the count room to double within half the scoped limit
    budget = get_kernel("resblock", "fp").vmem_budget
    assert resblock_vmem_bytes(112, 112, 32, 32) <= budget
    assert 2 * budget <= VMEM_LIMIT_BYTES // 2
    assert resblock_vmem_bytes(112, 112, 48, 48) == float("inf")
    assert resblock_vmem_bytes(112, 112, 32, 64) == float("inf")


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def test_vmem_count_holds_every_buffer_and_band_value_of_the_kernel():
    """At L2's stem the count is the bytes of every ref the kernel is
    given (its blocks and scratches, read from the traced kernel) plus
    one band's values inside the band loops: the accumulator, the tap
    and its product (each a dot's size) and the largest band stored.
    The fill before the loops writes into the scratches counted."""
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32) for s in
            [(1, 112, 112, 32), (3, 3, 32, 32), (32,), (3, 3, 32, 32),
             (32,)]]
    jaxpr = jax.make_jaxpr(lambda *a: resblock_fused(*a, interpret=True))(
        *args).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]

    def nbytes(aval):
        return int(np.prod(aval.shape)) * aval.dtype.itemsize

    refs = sum(nbytes(v.aval) for v in kernel.invars)
    loops = [e for e in kernel.eqns if e.primitive.name == "scan"]
    assert len(loops) == 2                # conv1's bands, then conv2's
    eqns = [q for e in loops for q in _eqns(e.params["jaxpr"])]
    dot = max(nbytes(e.outvars[0].aval) for e in eqns
              if e.primitive.name == "dot_general")
    stored = max(nbytes(e.invars[1].aval) for e in eqns
                 if e.primitive.name == "swap")
    assert len(kernel.invars) == 8        # 5 inputs, 1 output, 2 scratches
    assert resblock_vmem_bytes(112, 112, 32, 32) == refs + 3 * dot + stored
