"""jit'd public wrappers around the Pallas kernels.

`mode` selects the implementation:
  - None / "pallas": the compiled Pallas kernel; needs a TPU backend, and
    ``mode=None`` anywhere else is an error rather than a silent fallback,
  - "interpret": the kernel body in the Pallas interpreter (CPU tests),
  - "reference": the pure-XLA online-softmax path with identical math
    (dry-run lowering; Pallas TPU kernels don't lower for the CPU backend).
Block sizes are fixed defaults; the SimFA-TPU autotuner does not set them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.models import attention as _attn


def _resolve(mode: Optional[str]) -> str:
    if mode is not None:
        return mode
    backend = jax.default_backend()
    if backend != "tpu":
        raise ValueError(
            f"mode=None runs the Pallas kernel, which needs a TPU (backend is "
            f"{backend!r}); pass mode='interpret' or mode='reference'")
    return "pallas"


def mha_forward(q, k, v, *, causal: bool = True, block_q: int = 128,
                block_k: int = 128, mode: Optional[str] = None):
    """Layout: q (B, L, H, D); k/v (B, S, Hkv, D) — model-side layout."""
    mode = _resolve(mode)
    if mode == "reference":
        return _attn.flash_ref(q, k, v, causal=causal, chunk=block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _fa.flash_attention(qt, kt, vt, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=(mode == "interpret"))
    return o.transpose(0, 2, 1, 3)


def decode_forward(q, k_cache, v_cache, cache_len, *, block_k: int = 512,
                   mode: Optional[str] = None, return_partials: bool = False):
    """Layout: q (B, 1, H, D); caches (B, S, Hkv, D) — model-side layout."""
    mode = _resolve(mode)
    B, L, H, D = q.shape
    if mode == "reference":
        if return_partials:
            valid = jnp.arange(k_cache.shape[1])[None, :] < jnp.reshape(cache_len, (-1, 1))
            o, m, l = _attn.decode_attend_partial(q, k_cache, v_cache, valid)
            return o[:, 0].reshape(B, H, D), m[:, 0].reshape(B, H), l[:, 0].reshape(B, H)
        return _attn.decode_attend(q, k_cache, v_cache, cache_len)
    qt = q.reshape(B, H, D)
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    out = _fd.flash_decode(qt, kt, vt, cache_len, block_k=block_k,
                           return_partials=return_partials,
                           interpret=(mode == "interpret"))
    if return_partials:
        return out
    return out.reshape(B, 1, H, D)
