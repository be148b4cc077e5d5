"""Compile the main-path Pallas kernels and the serving engine's decode
step for a described (not attached) TPU v5e at real model widths.

Interpret mode cannot see Mosaic's block-shape and VMEM rules; the TPU
compiler can, without a chip. Each case lowers and compiles for one chip
of a ``v5e:2x2`` topology and checks that the kernel really is in the
program (``tpu_custom_call``), or what the decode step's buffers and
optimized HLO hold. The topology is described inside a fixture,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.llama3 import AttnWorkload
from repro.core.tpu.autotune import autotune_flash
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.launch.serve import init_params
from repro.serve.decode import cache_axes, compile_serve_step

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


def _shapes(text):
    return {tuple(int(d) for d in ds.split(",") if d)
            for ds in re.findall(r"\w+\[([\d,]*)\]", text)}


def _hbm_copies(hlo, dims, *, staged=False):
    """Copies and fusions of the optimized HLO text ``hlo`` that write an
    array of one of the shapes ``dims`` to HBM, outside any fused
    computation, other than a fusion whose root dynamic-update-slices an
    update of none of those shapes into one of its own parameters (in
    place). A fusion into VMEM (memory space ``S(1)``) stages what its
    consumer reads from HBM; it writes nothing there, and counts only
    where ``staged``: such a staging copy is still a read of the whole
    array and a write of it."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line.strip())
    fused = {c for lines in comps.values() for ln in lines if " fusion(" in ln
             for c in re.findall(r"calls=%([^\s,]+)", ln)}

    def in_place(fusion_line):
        callee = re.search(r"calls=%([^\s,]+)", fusion_line).group(1)
        lines = comps[callee]
        root = next(ln for ln in lines if ln.startswith("ROOT "))
        m = re.search(r"dynamic-update-slice\(%([^\s,]+), %([^\s,]+),",
                      root)
        if m is None:
            return False
        defs = {ln.split(" = ")[0]: ln for ln in lines}
        base, update = defs[f"%{m.group(1)}"], defs[f"%{m.group(2)}"]
        return " parameter(" in base and not (
            _shapes(update.split(" = ")[1].split("(")[0]) & dims)

    found = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for ln in lines:
            m = re.match(r"(?:ROOT )?%\S+ = (.+?) (copy|fusion)\(", ln)
            if m is None or ("S(1)" in m.group(1) and not staged):
                continue
            if _shapes(m.group(1)) & dims and not (m.group(2) == "fusion"
                                      and in_place(ln)):
                found.append(ln[:160])
    return found


@pytest.mark.parametrize("arch,slots,max_seq", [
    ("olmo-1b", 16, 2048),            # olmo-1b.long-decode's engine
    ("qwen2.5-3b", 8, 1152),          # qwen2.5-3b.long-prompt's engine
])
def test_decode_step_updates_the_cache_in_place(one_chip, arch, slots,
                                                max_seq):
    """The engine's decode step at a cell's full width: the K/V cache is
    donated and written one position per layer, so the step aliases the
    whole cache, needs less scratch than one layer's K, and neither slices
    a layer out nor writes one (or the whole cache) back, in either axis
    order. The cache it takes is in the default layout, which an
    executable read back from JAX's persistent cache keeps."""
    cfg = registry.get(arch)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, 0)))
    shape = dict(batch=slots, max_seq=max_seq, sharding=one_chip)
    axes = cache_axes(cfg, params, **shape)
    compiled = compile_serve_step(cfg, params, axes, **shape)
    L, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    stack = (L, slots, max_seq, hkv, hd)
    stored = tuple(stack[i] for i in axes["k"])
    dims = {d for s in (stack, stored) for d in (s, s[1:], (1,) + s[1:])}
    layer_bytes = 2 * slots * max_seq * hkv * hd
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * L * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes
    assert _hbm_copies(compiled.as_text(), dims) == []
    for f in jax.tree.leaves(compiled.input_formats[0][1]):
        assert f.layout.major_to_minor == tuple(range(len(
            f.layout.major_to_minor)))


def test_latent_decode_step_updates_the_cache_in_place(one_chip):
    """moonlight-16b-a3b.long-decode-32's engine step (32 slots, 2048
    positions, latent attention over 8 held of 64 routed experts, at full
    width): the latent cache (c_kv and k_pe, the leading dense layer's in
    arrays of their own) is donated and written one position per layer, so
    the step aliases at least the whole cache, needs less scratch than one
    layer's latent, and never copies a layer or an array whole; it also
    returns its routed counts."""
    import dataclasses
    cfg = dataclasses.replace(registry.get("moonlight-16b-a3b"),
                              experts_held=8)
    slots, max_seq = 32, 2048
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, 0)))
    shape = dict(batch=slots, max_seq=max_seq, sharding=one_chip)
    axes = cache_axes(cfg, params, **shape)
    compiled = compile_serve_step(cfg, params, axes, **shape)
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    dims = set()
    for name, w in (("c_kv", cfg.kv_lora_rank), ("k_pe", cfg.qk_rope_head_dim)):
        for n, arr in ((cfg.num_layers - 1, name), (1, "dense_" + name)):
            stack = (n, slots, max_seq, w)
            stored = tuple(stack[i] for i in axes[arr])
            dims |= {d for s in (stack, stored)
                     for d in (s, s[1:], (1,) + s[1:])}
    layer_bytes = 2 * slots * max_seq * width
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cfg.num_layers * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes
    assert _hbm_copies(compiled.as_text(), dims) == []
    routed = compiled.out_info[2]
    assert routed.shape == (cfg.num_layers - 1, 8)


def test_latent_decode_attention_is_one_kernel_over_the_stored_cache(
        one_chip):
    """moonlight-16b-a3b.long-decode-32's engine step (32 slots, 2048
    positions): its latent decode attention is the Pallas kernel
    ``mla_decode_attend``, which reads each layer's c_kv and k_pe where the
    stacked cache stores them. No fusion or copy outside it writes an
    array of one layer's latent (or of the whole stack), into HBM or
    staged into VMEM, and the step's scratch stays under one layer's
    latent."""
    import dataclasses
    cfg = dataclasses.replace(registry.get("moonlight-16b-a3b"),
                              experts_held=8)
    slots, max_seq = 32, 2048
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, 0)))
    shape = dict(batch=slots, max_seq=max_seq, sharding=one_chip)
    axes = cache_axes(cfg, params, **shape)
    compiled = compile_serve_step(cfg, params, axes, **shape)
    hlo = compiled.as_text()
    kernels = re.findall(r"%mla_decode_attend\S* = .*tpu_custom_call", hlo)
    assert len(kernels) == 2        # the leading dense layer, the scan body
    dims = set()
    for name, w in (("c_kv", cfg.kv_lora_rank),
                    ("k_pe", cfg.qk_rope_head_dim)):
        for n, arr in ((cfg.num_layers - 1, name), (1, "dense_" + name)):
            stack = (n, slots, max_seq, w)
            stored = tuple(stack[i] for i in axes[arr])
            dims |= {d for s in (stack, stored)
                     for d in (s, s[1:], (1,) + s[1:])}
    assert _hbm_copies(hlo, dims, staged=True) == []
    latent_bytes = 2 * slots * max_seq * (cfg.kv_lora_rank
                                          + cfg.qk_rope_head_dim)
    assert compiled.memory_analysis().temp_size_in_bytes < latent_bytes
