"""Operations and bytes of a DeepSeek-V3-style LM (latent attention, routed
and shared experts, leading dense layers) at one chip's share of its
experts, from its configuration file's sizes alone: the work the algorithm
needs, whatever implements it.

``conf`` is a configuration file's dict (Hugging Face key names, and the
``deployment`` it states); ``n_routed_experts`` is the experts held here.
FLOPs count a multiply-add as two. A decode step is counted absorbed (the
query is taken into the latent, and ``W_UV`` applied after the weighted
sum), at the context each token really attends to, and the routed experts
at the (token, choice) pairs they were really given.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np


def _dims(conf):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"])


def latent_width(conf) -> int:
    """Values a token keeps per layer: the latent and the rope key."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def moe_layers(conf) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def attn_params(conf) -> int:
    """One layer's attention matmuls: W_q, W_kv_a, W_kv_b, W_o."""
    d, h, r, nope, rope, v = _dims(conf)
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d


def expert_params(conf) -> int:
    """One routed expert's SwiGLU."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def router_params(conf) -> int:
    return conf["hidden_size"] * \
        conf["deployment"]["published_n_routed_experts"]


def shared_params(conf) -> int:
    return conf["n_shared_experts"] * expert_params(conf)


def dense_mlp_params(conf) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def embedding_params(conf) -> int:
    n = conf["vocab_size"] * conf["hidden_size"]
    return n if conf["tie_word_embeddings"] else 2 * n


def matmul_params(conf) -> int:
    """Every matmul weight held here, the embedding and the head included."""
    L = conf["num_hidden_layers"]
    return L * attn_params(conf) \
        + conf["first_k_dense_replace"] * dense_mlp_params(conf) \
        + moe_layers(conf) * (conf["n_routed_experts"] * expert_params(conf)
                              + shared_params(conf) + router_params(conf)) \
        + embedding_params(conf)


def weight_count(conf) -> int:
    """Every stored weight: the matmuls, the norm scales (two a layer, the
    latent's, the final one) and the router's bias."""
    d, L = conf["hidden_size"], conf["num_hidden_layers"]
    norms = L * (2 * d + conf["kv_lora_rank"]) + d
    bias = moe_layers(conf) * conf["deployment"]["published_n_routed_experts"]
    return matmul_params(conf) + norms + bias


def latent_bytes_per_token(conf, itemsize: int = 2) -> int:
    """The latent cache of one position over all layers."""
    return conf["num_hidden_layers"] * latent_width(conf) * itemsize


def token_flops(conf, context: int) -> int:
    """One decode token through every layer, absorbed, but the routed
    experts (:func:`routed_flops`), and the unembed; attending to
    ``context`` positions (its own included)."""
    d, h, r, nope, rope, v = _dims(conf)
    L = conf["num_hidden_layers"]
    attn = 2 * (d * h * (nope + rope) + d * (r + rope)   # W_q, W_kv_a
                + h * nope * r                            # q_nope W_UK^T
                + h * (r + rope) * context                # scores
                + h * r * context                         # weighted latent
                + h * r * v + h * v * d)                  # W_UV, W_o
    mlps = conf["first_k_dense_replace"] * 2 * dense_mlp_params(conf) \
        + moe_layers(conf) * 2 * (shared_params(conf) + router_params(conf))
    return L * attn + mlps + 2 * d * conf["vocab_size"]


def routed_flops(conf, pairs: int) -> int:
    """``pairs`` (token, choice) pairs through a held expert."""
    return 2 * expert_params(conf) * pairs


def decode_flops(conf, contexts: Iterable[int],
                 routed: Sequence[Sequence[int]]) -> int:
    """One decode step: one new token per slot, slot ``i`` attending to
    ``contexts[i]`` positions; ``routed`` (MoE layers, held experts): the
    pairs each held expert was given."""
    return sum(token_flops(conf, c) for c in contexts) \
        + routed_flops(conf, int(np.sum(routed)))


def decode_bytes(conf, contexts: Iterable[int],
                 routed: Sequence[Sequence[int]], itemsize: int = 2) -> int:
    """One decode step's least HBM traffic: every weight read once, but the
    input table, of which the step's rows are read, and the held experts,
    of which only those given a token in that step are read; each slot's
    filled latent read, and the new one written."""
    contexts = list(contexts)
    d = conf["hidden_size"]
    held = moe_layers(conf) * conf["n_routed_experts"] * expert_params(conf)
    active = int(np.count_nonzero(np.asarray(routed)))
    weights = weight_count(conf) - conf["vocab_size"] * d - held \
        + len(contexts) * d + active * expert_params(conf)
    lat = latent_bytes_per_token(conf, itemsize)
    return weights * itemsize + lat * (sum(contexts) + len(contexts))
