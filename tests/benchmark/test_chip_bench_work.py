"""work.py against hand counts for both configurations, and the peaks
table keyed by device kind."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import work

REPO = Path(__file__).resolve().parents[2]

CONFIGS = REPO / "benchmarks" / "chip" / "configs"


#: OLMoE-1B-7B-0924's published widths (hf allenai/OLMoE-1B-7B-0924
#: config.json) at 8 of its 16 layers
OLMOE = {"config": {
    "hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 8,
    "num_attention_heads": 16, "num_key_value_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "vocab_size": 50304}}


def _dims(name):
    return work.Dims.of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen3_hand_counts():
    d = _dims("qwen3-14b-pp4")
    # per layer: wq, wo 5120x5120; wk, wv 5120x1024; SwiGLU 3 x 5120x17408
    per_layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert per_layer == 330_301_440
    assert d.body_flops_per_token == 2 * per_layer * 10
    assert d.kv_bytes_per_token == 8 * 128 * 2 * 2 * 10 == 40_960
    head = 5120 * 151936 * 2
    weights = 10 * (per_layer * 2 + 4 * 5120 * 2) + head + 16 * 5120 * 2
    flops, nbytes = work.decode_work(d, [1000] * 16)
    assert nbytes == pytest.approx(weights + 16 * 1000 * 40_960)
    assert flops == pytest.approx(
        16 * (2 * per_layer * 10 + 2 * 5120 * 151936
              + 4 * 40 * 128 * 1000 * 10))


def test_olmoe_hand_counts():
    d = work.Dims.of(OLMOE)
    attn = 4 * 2048 * 2048
    routed = 8 * 3 * 2048 * 1024          # top-8 of 64 experts
    router = 2048 * 64
    assert d.body_flops_per_token == 2 * (attn + routed + router) * 8
    # one token touches its 8 experts; a full chunk touches all 64
    one = d.weight_bytes(1)
    many = d.weight_bytes(512)
    expert = 3 * 2048 * 1024 * 2
    assert one == pytest.approx(8 * (attn * 2 + 4 * 2048 * 2 + 8 * expert
                                     + router * 4)
                                + 2048 * 50304 * 2 + 2048 * 2)
    assert many - 512 * 2048 * 2 == pytest.approx(
        8 * (attn * 2 + 4 * 2048 * 2 + 64 * expert + router * 4)
        + 2048 * 50304 * 2, rel=1e-6)


def test_chunk_attention_is_causal():
    d = _dims("qwen3-14b-pp4")
    flops, nbytes = work.chunk_work(d, [(100, 4)])
    seen = 101 + 102 + 103 + 104
    assert flops == pytest.approx(4 * d.body_flops_per_token + d.head_flops
                                  + d.attn_flops(1) * seen)
    assert nbytes == pytest.approx(d.weight_bytes(4) + 104 * 40_960)


def test_least_time_names_its_bound():
    peaks = work.load_peaks("TPU v5 lite")
    assert work.least_time(197e12, 1.0, peaks) == (1.0, "compute")
    assert work.least_time(1.0, 819e9, peaks) == (1.0, "memory")


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")
