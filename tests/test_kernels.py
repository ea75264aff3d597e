"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU; TPU is the target; the
public wrappers pick interpret mode from the platform)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dep — fall back to the seeded mini-sampler
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.kernels.fingerprint.kernel import fingerprint
from repro.kernels.fingerprint.ops import tree_digest_hex
from repro.kernels.fingerprint.ref import digest_hex, fingerprint_ref
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import flash_gqa
from repro.kernels.mamba_ssd.ops import ssd
from repro.kernels.mamba_ssd.ref import ssd_reference

KEY = jax.random.PRNGKey(0)


# ========================================================== flash attention
def _fa_case(B, Hq, Hkv, S, T, d, dtype=jnp.float32, **kw):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, d), dtype)
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True,
                          **kw)
    kr = jnp.repeat(k, Hq // Hkv, axis=1)
    vr = jnp.repeat(v, Hq // Hkv, axis=1)
    exp = fa_ref.mha_reference(q, kr, vr, causal=kw.get("causal", True),
                               window=kw.get("window"),
                               softcap=kw.get("softcap"))
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol)


@pytest.mark.parametrize("B,Hq,Hkv,S,T,d", [
    (1, 2, 2, 32, 32, 16),   # MHA
    (2, 4, 2, 32, 32, 16),   # GQA 2:1
    (1, 8, 1, 16, 16, 8),    # MQA
    (1, 2, 1, 24, 24, 16),   # ragged S
    (1, 2, 2, 16, 48, 16),   # T > S (prefix cache)
    (1, 3, 3, 40, 72, 32),   # ragged everything
])
def test_flash_shapes(B, Hq, Hkv, S, T, d):
    _fa_case(B, Hq, Hkv, S, T, d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    _fa_case(1, 2, 2, 32, 32, 16, dtype=dtype)


@pytest.mark.parametrize("kw", [
    dict(window=8),                       # gemma2 local layer
    dict(softcap=50.0),                   # gemma2 logit cap
    dict(window=12, softcap=30.0),
    dict(causal=False),
])
def test_flash_options(kw):
    _fa_case(1, 4, 2, 32, 32, 16, **kw)


def test_flash_gqa_wrapper_and_grad():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 24, 4, 16))
    k = jax.random.normal(ks[1], (2, 24, 2, 16))
    v = jax.random.normal(ks[2], (2, 24, 2, 16))

    def loss_kernel(q, k, v):
        return jnp.sum(flash_gqa(q, k, v) ** 2)

    def loss_ref(q, k, v):
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        kr = jnp.repeat(kt, 2, axis=1)
        vr = jnp.repeat(vt, 2, axis=1)
        out = fa_ref.mha_reference(qt, kr, vr, causal=True)
        return jnp.sum(jnp.swapaxes(out, 1, 2) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(S=st.integers(8, 48), d=st.sampled_from([8, 16, 32]),
       Hkv=st.sampled_from([1, 2]), groups=st.sampled_from([1, 2, 4]))
def test_property_flash_matches_ref(S, d, Hkv, groups):
    _fa_case(1, Hkv * groups, Hkv, S, S, d)


# ===================================================================== SSD
def _ssd_case(B, S, nh, hd, ns, chunk, h0=False, dtype=jnp.float32):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh), dtype))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (B, S, ns), dtype)
    Cm = jax.random.normal(ks[4], (B, S, ns), dtype)
    h0v = (0.1 * jax.random.normal(ks[0], (B, nh, hd, ns), jnp.float32)
           if h0 else None)
    y, h = ssd(x, dt, A, Bm, Cm, chunk=chunk, h0=h0v)
    ye, he = ssd_reference(x, dt, A, Bm, Cm, h0=h0v)
    tol = 3e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), atol=tol,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=1e-3)


@pytest.mark.parametrize("B,S,nh,hd,ns,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 16, 16, 32),
    (1, 100, 2, 16, 8, 32),   # ragged tail → identity-step padding
    (1, 64, 2, 16, 8, 64),    # single chunk
    (1, 32, 1, 8, 4, 8),
])
def test_ssd_shapes(B, S, nh, hd, ns, chunk):
    _ssd_case(B, S, nh, hd, ns, chunk)


def test_ssd_initial_state():
    _ssd_case(1, 64, 2, 16, 8, 16, h0=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_dtypes(dtype):
    _ssd_case(2, 96, 3, 8, 4, 16, dtype=dtype)


@settings(max_examples=8, deadline=None)
@given(nchunks=st.integers(1, 4), chunk=st.sampled_from([8, 16]),
       nh=st.integers(1, 3))
def test_property_ssd_chunking_invariant(nchunks, chunk, nh):
    """Chunk size must not change the result (pure decomposition)."""
    S = nchunks * chunk
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (1, S, nh, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (1, S, 4))
    Cm = jax.random.normal(ks[4], (1, S, 4))
    y1, h1 = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y2, h2 = ssd(x, dt, A, Bm, Cm, chunk=S)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


# ============================================================= fingerprint
FP_CASES = [
    ((100,), jnp.float32), ((33, 7), jnp.float32), ((1000,), jnp.bfloat16),
    ((5,), jnp.int32), ((3,), jnp.uint8), ((17,), jnp.bool_),
    ((4096,), jnp.float32), ((1,), jnp.float32),
]


@pytest.mark.parametrize("shape,dtype", FP_CASES)
def test_fingerprint_matches_ref_bitexact(shape, dtype):
    if dtype == jnp.bool_:
        x = jax.random.bernoulli(KEY, 0.5, shape)
    elif jnp.issubdtype(dtype, jnp.integer):
        x = jax.random.randint(KEY, shape, 0, 100).astype(dtype)
    else:
        x = jax.random.normal(KEY, shape).astype(dtype)
    dk = fingerprint(x, block_rows=8, interpret=True)
    dr = fingerprint_ref(x)
    assert (np.asarray(dk) == np.asarray(dr)).all()


def test_fingerprint_block_size_invariant():
    x = jax.random.normal(KEY, (7777,))  # 61 rows of 128 words
    d1 = fingerprint(x, block_rows=8, interpret=True)
    d2 = fingerprint(x, block_rows=64, interpret=True)
    assert (np.asarray(d1) == np.asarray(d2)).all()


def test_fingerprint_sensitivity():
    x = jax.random.normal(KEY, (257,))
    y = x.at[200].add(1e-7)
    assert digest_hex(fingerprint_ref(x)) != digest_hex(fingerprint_ref(y))
    # length extension: [x, 0] != [x]
    x0 = jnp.pad(x, (0, 1))
    assert digest_hex(fingerprint_ref(x)) != digest_hex(fingerprint_ref(x0))


def test_tree_digest_stable_across_orders():
    a = jax.random.normal(KEY, (16,))
    b = jax.random.normal(jax.random.PRNGKey(9), (8, 2))
    d1 = tree_digest_hex({"a": a, "b": b})
    d2 = tree_digest_hex({"b": b, "a": a})
    assert d1 == d2
    assert d1 != tree_digest_hex({"a": a, "b": b + 1})


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 3000), seed=st.integers(0, 99))
def test_property_fingerprint_kernel_equals_ref(n, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    dk = fingerprint(x, block_rows=8, interpret=True)
    dr = fingerprint_ref(x)
    assert (np.asarray(dk) == np.asarray(dr)).all()
