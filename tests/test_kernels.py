"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU) + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep: degrade, don't die
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.models import attention

KEY = jax.random.PRNGKey(0)


def _tol(dt):
    return dict(atol=2e-2, rtol=2e-2) if dt == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,Hkv,L,S,D", [
    (2, 4, 2, 128, 128, 64),
    (1, 8, 2, 256, 256, 128),
    (2, 4, 4, 100, 100, 64),      # non-multiple of block
    (1, 4, 1, 64, 384, 128),      # cross(L != S)
    (1, 2, 2, 192, 192, 112),     # zamba head_dim
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_matches_ref(B, H, Hkv, L, S, D, causal, dtype):
    if causal and L != S:
        pytest.skip("causal path assumes aligned self-attention")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, L, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                        interpret=True)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,Hkv,S,D,clen", [
    (2, 8, 2, 512, 64, 300),
    (1, 16, 8, 1024, 128, 1024),
    (2, 4, 4, 256, 64, 1),
    (1, 6, 1, 640, 128, 77),      # G=6 (dbrx-like), ragged length
])
@pytest.mark.parametrize("partials", [False, True])
def test_flash_decode_matches_ref(B, H, Hkv, S, D, clen, partials):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, S, D))
    vc = jax.random.normal(ks[2], (B, Hkv, S, D))
    o_ref = ref.flash_decode_ref(q, kc, vc, jnp.full((B,), clen))
    if partials:
        acc, m, l = flash_decode(q, kc, vc, clen, block_k=128,
                                 return_partials=True, interpret=True)
        o = acc / jnp.maximum(l, 1e-30)[..., None]
    else:
        o = flash_decode(q, kc, vc, clen, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=2e-5, rtol=2e-5)


def test_block_size_invariance():
    """Online softmax result must not depend on the tiling."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


def test_ops_reference_path_matches_kernel():
    """ops.mha_forward('reference') == ops.mha_forward('interpret')."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))     # model layout (B,L,H,D)
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    o1 = ops.mha_forward(q, k, v, causal=True, mode="reference")
    o2 = ops.mha_forward(q, k, v, causal=True, mode="interpret", block_q=64,
                         block_k=64)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-5, rtol=1e-5)


def test_decode_partial_merge_distributed_equivalence():
    """Sharded (o,m,l) partials merged across 4 sequence shards == global."""
    ks = jax.random.split(KEY, 3)
    B, H, Hkv, S, D = 2, 8, 4, 512, 64
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    clen = 400
    o_ref = ops.decode_forward(q, kc, vc, clen, mode="reference")
    parts = []
    for i in range(4):
        sl = slice(i * S // 4, (i + 1) * S // 4)
        valid = (jnp.arange(S)[sl][None, :] < clen) & jnp.ones((B, 1), bool)
        o, m, l = attention.decode_attend_partial(q, kc[:, sl], vc[:, sl], valid)
        parts.append((o, m, l))
    o = attention.merge_partial_attn(
        jnp.stack([p[0] for p in parts]), jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]))
    np.testing.assert_allclose(np.asarray(o[:, 0].reshape(B, 1, H, D)),
                               np.asarray(o_ref, np.float32), atol=1e-5, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    L=st.sampled_from([64, 96, 128, 160]),
    H=st.sampled_from([2, 4]),
    G=st.sampled_from([1, 2]),
    D=st.sampled_from([64, 128]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_fwd_property(L, H, G, D, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, H * G, L, D))
    k = jax.random.normal(ks[1], (1, H, L, D))
    v = jax.random.normal(ks[2], (1, H, L, D))
    o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)
    # property: rows are convex combinations of V rows -> bounded by V range
    assert float(jnp.max(jnp.abs(o))) <= float(jnp.max(jnp.abs(v))) + 1e-4


@settings(max_examples=15, deadline=None)
@given(
    S=st.sampled_from([128, 256, 384]),
    clen=st.integers(1, 384),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_decode_property(S, clen, seed):
    clen = min(clen, S)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 4, 64))
    kc = jax.random.normal(ks[1], (1, 2, S, 64))
    vc = jax.random.normal(ks[2], (1, 2, S, 64))
    o = flash_decode(q, kc, vc, clen, block_k=128, interpret=True)
    o_ref = ref.flash_decode_ref(q, kc, vc, jnp.full((1,), clen))
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("fn", ["mha_forward", "decode_forward"])
def test_ops_default_mode_needs_tpu(fn):
    """mode=None means the compiled kernel; off the TPU that is an error,
    never a quiet switch to the reference."""
    if jax.default_backend() == "tpu":
        pytest.skip("runs the kernel on a TPU")
    q = jnp.zeros((1, 1, 4, 64))
    kv = jnp.zeros((1, 128, 2, 64))
    args = (q, kv, kv) if fn == "mha_forward" else (q, kv, kv, 1)
    with pytest.raises(ValueError, match="needs a TPU"):
        getattr(ops, fn)(*args)
