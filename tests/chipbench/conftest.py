"""Shared fixtures of the benchmark's CPU tests: the repository root and
the program on ``sys.path``, and cells of the benchmark cut to a width the
CPU serves in seconds."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "head_dim": 16, "vocab_size": 256}
TINY_TRAFFIC = {"clients": 4, "slots": 4, "prompt_len": 16, "max_new": 8,
                "max_seq": 24, "check_per_slot": 1, "check_block": 2}
# The widest logit gap of served tokens at this width: the program read 0
# to 0.017 over seeds 0-5 of both cells, the float8 control 0.045 to 0.24.
# The full cells' limits are set from chip readings at their own sizes.
TINY_LIMIT = 0.03


def tiny_cell(name: str, **traffic) -> spec.Cell:
    """Cell ``name`` of BENCHMARK.json at a width the CPU serves fast; the
    K/V head count keeps the configuration's kind (GQA stays GQA)."""
    cell = copy.deepcopy(spec.cell(name, ROOT))
    conf = cell.config
    kv = 2 if conf["num_key_value_heads"] < conf["num_attention_heads"] \
        else TINY["num_attention_heads"]
    conf.update(TINY, num_key_value_heads=kv)
    cell.traffic.update(TINY_TRAFFIC, **traffic)
    cell.limits = {"max_logit_gap": TINY_LIMIT}
    return cell


@pytest.fixture
def qwen_tiny():
    return tiny_cell("qwen2.5-3b.long-prompt")


@pytest.fixture
def olmo_tiny():
    return tiny_cell("olmo-1b.long-decode")
