"""Family-dispatching model facade used by train/serve/launch layers.

Batch contract (all jnp arrays):
  train:   {"tokens": (B,S_tok), "labels": (B,S_tok), ["embeds"|"frames"]}
  prefill: {"tokens": (B,S_tok), ["embeds"|"frames"]}
  decode:  {"tokens": (B,1)} + cache
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.models import encdec, transformer


def init(cfg, key):
    if cfg.family == "encdec":
        return encdec.init_encdec(cfg, key)
    return transformer.init_lm(cfg, key)


def forward_hidden(cfg, params, batch: Dict[str, Any], *, attn_fn=None,
                   remat: str = "full"):
    """Training forward to final hidden states. Returns (hidden, aux)."""
    if cfg.family == "encdec":
        enc_h = encdec.encode(cfg, params, batch["frames"], attn_fn=attn_fn,
                              remat=remat)
        hidden = encdec.decode_train(cfg, params, batch["tokens"], enc_h,
                                     attn_fn=attn_fn, remat=remat)
        return hidden, 0.0
    hidden, aux, _ = transformer.apply_lm(
        cfg, params, batch["tokens"], embeds=batch.get("embeds"),
        attn_fn=attn_fn, remat=remat)
    return hidden, aux


def unembed(cfg, params, hidden):
    if cfg.family == "encdec":
        dt = jnp.dtype(cfg.compute_dtype)
        return hidden.astype(dt) @ params["emb"]["table"].T.astype(dt)
    return transformer.unembed(cfg, params, hidden)


def unembed_table(cfg, params):
    """(d, V) matrix used by the chunked loss."""
    if cfg.family == "encdec" or cfg.tie_embeddings:
        return params["emb"]["table"].T
    return params["unembed"]["w"]


def prefill(cfg, params, batch, *, max_seq=None, remat: str = "full",
            attn_fn=None):
    if cfg.family == "encdec":
        return encdec.prefill_encdec(cfg, params, batch["frames"],
                                     batch["tokens"], max_seq=max_seq,
                                     remat=remat)
    return transformer.prefill_lm(cfg, params, batch["tokens"],
                                  embeds=batch.get("embeds"),
                                  max_seq=max_seq, remat=remat,
                                  attn_fn=attn_fn)


def decode(cfg, params, cache, tokens):
    """(logits, cache), and the routed counts where ``cfg.reports_routing``
    (``transformer.decode_lm``)."""
    if cfg.family == "encdec":
        return encdec.decode_encdec(cfg, params, cache, tokens)
    return transformer.decode_lm(cfg, params, cache, tokens)


def init_cache(cfg, batch: int, max_seq: int, *, s_enc: int = 0, dtype=jnp.bfloat16):
    if cfg.family == "encdec":
        return encdec.init_dec_cache(cfg, batch, max_seq, s_enc or cfg.frontend_len,
                                     dtype=dtype)
    return transformer.init_cache(cfg, batch, max_seq, dtype=dtype)
