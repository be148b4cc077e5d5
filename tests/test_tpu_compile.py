"""Compile the main-path Pallas kernels for a described (not attached)
TPU v5e at real model widths.

Interpret mode cannot see Mosaic's block-shape and VMEM rules; the TPU
compiler can, without a chip. Each case lowers and compiles for one chip
of a ``v5e:2x2`` topology and checks that the kernel really is in the
program (``tpu_custom_call``). The topology is described inside a fixture,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.llama3 import AttnWorkload
from repro.core.tpu.autotune import autotune_flash
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode

# qwen2.5-3b attention widths: 16 query heads over 2 KV heads, head_dim 128
H, HKV, D = 16, 2, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _autotuned_prefill_blocks():
    plan = autotune_flash(AttnWorkload(name="prefill", B=1, L=2048, S=2048,
                                       H_kv=HKV, G=H // HKV, D=D, causal=True))
    return plan.block_q, plan.block_k


@pytest.mark.parametrize("blocks", ["default", "autotuned"])
def test_flash_attention_compiles_at_qwen_width(one_chip, blocks):
    kw = {}
    if blocks == "autotuned":
        kw = dict(zip(("block_q", "block_k"), _autotuned_prefill_blocks()))
    q = _spec(one_chip, (1, H, 2048, D))
    kv = _spec(one_chip, (1, HKV, 2048, D))
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, **kw)
    ).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("hkv", [HKV, H], ids=["gqa_g8", "mha_g1"])
@pytest.mark.parametrize("partials", [False, True])
def test_flash_decode_compiles(one_chip, hkv, partials):
    """G=8 is qwen2.5-3b; G=1 is olmo-1b (16 KV heads)."""
    B, S = 4, 4096
    q = _spec(one_chip, (B, H, D))
    kv = _spec(one_chip, (B, hkv, S, D))
    n = _spec(one_chip, (), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, n: flash_decode(q, k, v, n, return_partials=partials)
    ).lower(q, kv, kv, n).compile()
    _assert_kernel(compiled)
