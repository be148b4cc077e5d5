"""Operations and bytes of a dense decoder-only LM, from its configuration
file's sizes alone: the work the algorithm needs, whatever implements it.

``conf`` is a configuration file's dict (Hugging Face key names). FLOPs
count a multiply-add as two. Attention is counted at the context each
token really attends to, not at the cache's allocated length.
"""
from __future__ import annotations

from typing import Iterable


def _dims(conf):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return d, h, hkv, hd


def layer_linear_params(conf) -> int:
    """Weights of one layer's matmuls: q, k, v, o and the gated MLP."""
    d, h, hkv, hd = _dims(conf)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d \
        + 3 * d * conf["intermediate_size"]


def embedding_params(conf) -> int:
    n = conf["vocab_size"] * conf["hidden_size"]
    return n if conf["tie_word_embeddings"] else 2 * n


def matmul_params(conf) -> int:
    """Every matmul weight, the embedding table(s) included."""
    return conf["num_hidden_layers"] * layer_linear_params(conf) \
        + embedding_params(conf)


def weight_count(conf) -> int:
    """Every stored weight: matmuls, embeddings, QKV biases, norm scales."""
    d, h, hkv, hd = _dims(conf)
    per_layer = layer_linear_params(conf)
    if conf.get("qkv_bias"):
        per_layer += (h + 2 * hkv) * hd
    norms = 0 if conf["norm"] == "nonparam_ln" else d
    per_layer += 2 * norms
    return conf["num_hidden_layers"] * per_layer + embedding_params(conf) \
        + norms


def kv_bytes_per_token(conf, itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    _, _, hkv, hd = _dims(conf)
    return 2 * conf["num_hidden_layers"] * hkv * hd * itemsize


def attention_flops(conf, context: int) -> int:
    """QK^T and PV of one query token over ``context`` positions, all
    layers."""
    _, h, _, hd = _dims(conf)
    return 4 * h * hd * context * conf["num_hidden_layers"]


def unembed_flops(conf) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def token_linear_flops(conf) -> int:
    return 2 * conf["num_hidden_layers"] * layer_linear_params(conf)


def prefill_flops(conf, prompt_len: int) -> int:
    """One prompt through every layer, causal attention, and the unembed of
    its last position only (what the engine computes)."""
    causal_ctx = prompt_len * (prompt_len + 1) // 2
    return prompt_len * token_linear_flops(conf) \
        + attention_flops(conf, causal_ctx) + unembed_flops(conf)


def decode_flops(conf, contexts: Iterable[int]) -> int:
    """One decode step: one new token per slot, slot ``i`` attending to
    ``contexts[i]`` positions (its own new one included)."""
    contexts = list(contexts)
    return len(contexts) * (token_linear_flops(conf) + unembed_flops(conf)) \
        + sum(attention_flops(conf, c) for c in contexts)


def decode_bytes(conf, contexts: Iterable[int], itemsize: int = 2) -> int:
    """One decode step's least HBM traffic: every weight read once, each
    slot's filled K/V read, and the new K/V written."""
    contexts = list(contexts)
    kv = kv_bytes_per_token(conf, itemsize)
    return weight_count(conf) * itemsize + kv * sum(contexts) \
        + kv * len(contexts)


def least_time_s(flops: float, nbytes: float, peak) -> tuple:
    """(seconds, bound): the larger of compute and memory time."""
    t_c = flops / peak.bf16_flops
    t_m = nbytes / peak.hbm_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
