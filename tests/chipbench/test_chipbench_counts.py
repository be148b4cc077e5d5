"""FLOPs and bytes from shapes, against the program's own parameter count,
the parameters it really makes, and counts by hand."""
from __future__ import annotations

import math

import jax
import pytest
from conftest import ROOT, TINY

from chipbench import counts, peaks, spec
from chipbench.drivers.serve_closed import program_config

CELLS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_matmul_params_match_the_program(name):
    conf = spec.cell(name, ROOT).config
    assert counts.matmul_params(conf) == program_config(conf).param_count()


@pytest.mark.parametrize("name", CELLS)
def test_weight_count_matches_the_parameters_made(name):
    from repro.models import api
    conf = spec.cell(name, ROOT).config
    shapes = jax.eval_shape(lambda k: api.init(program_config(conf), k),
                            jax.random.PRNGKey(0))
    made = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert counts.weight_count(conf) == made


def test_kv_bytes_per_token_as_published():
    q = spec.cell("qwen2.5-3b.long-prompt", ROOT).config
    o = spec.cell("olmo-1b.long-decode", ROOT).config
    assert counts.kv_bytes_per_token(q) == 36 * 2 * 2 * 128 * 2 == 36864
    assert counts.kv_bytes_per_token(o) == 16 * 2 * 16 * 128 * 2 == 131072


def test_hand_counts_at_tiny_width():
    conf = dict(TINY, num_key_value_heads=2, tie_word_embeddings=True,
                norm="rmsnorm", qkv_bias=True)
    d, ff, L, H, hd, V = 64, 128, 2, 4, 16, 256
    per_layer = d * H * hd + 2 * d * 2 * hd + H * hd * d + 3 * d * ff
    assert counts.layer_linear_params(conf) == per_layer
    tok = 2 * L * per_layer
    # prompt of 3: 3 tokens of matmuls, causal attention over 1+2+3
    # positions, the last position's unembed
    assert counts.prefill_flops(conf, 3) == \
        3 * tok + 4 * H * hd * 6 * L + 2 * d * V
    assert counts.decode_flops(conf, [5, 7]) == \
        2 * (tok + 2 * d * V) + 4 * H * hd * 12 * L
    weights = L * (per_layer + (H + 4) * hd + 2 * d) + V * d + d
    kv = 2 * L * 2 * hd * 2
    assert counts.weight_count(conf) == weights
    assert counts.decode_bytes(conf, [5, 7]) == 2 * weights + kv * 12 + kv * 2


def test_least_time_names_its_bound():
    pk = peaks.peak("TPU v5 lite")
    assert counts.least_time_s(197e12, 1.0, pk) == (1.0, "compute")
    t, bound = counts.least_time_s(1.0, 819e9, pk)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
