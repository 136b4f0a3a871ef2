"""A copy of the chip benchmark with tiny cells added, for CPU tests.

The tiny configurations build the same program families as the real ones
(``qwen3-14b`` dense, ``olmoe-1b-7b`` experts) at widths a test can hold;
they and their mixes, cells and ``BENCHMARK.json`` entries are *added* to
the copy, the way a later change adds a cell.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: the tiny dense cell's mean-gap limit. Read on the CPU over seeds 1-12
#: and 2**31 + 11: the program's mean gap is 0.0 on every one, the fp8
#: control's 0.0072-0.0375
TINY_MEAN_LIMIT = 0.002

TINY_CONFIGS = {
    "tiny-dense": {"arch": "qwen3-14b", "config": {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16"}},
    "tiny-moe": {"arch": "olmoe-1b-7b", "config": {
        "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True, "vocab_size": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16"}},
}
TINY_MIXES = {
    "tiny-backlog": {"arrival": {"kind": "backlog", "depth": 2}},
    "tiny-poisson": {"arrival": {"kind": "poisson", "rate": 20.0}},
}
#: name: (config, mix, metric suffix, check rule). The dense cell is
#: judged as the committed cell is, by the mean logit gap. At these widths top-2 of 8 experts
#: flips its choice on one rounding and a flip moves a logit by up to
#: 2.4, so the tiny MoE cell drives the harness's paths and is judged on
#: its widest gap with a limit no sound run reaches
TINY_CELLS = {
    "tiny-dense-batch": ("tiny-dense", "tiny-backlog", ".batch",
                         {"statistic": "mean", "limit": TINY_MEAN_LIMIT}),
    "tiny-moe-slo": ("tiny-moe", "tiny-poisson", ".slo",
                     {"statistic": "widest", "limit": 0.5})}


def add_tiny_cells(root: Path) -> None:
    """Add the tiny configurations, mixes and cells under ``root`` (a copy
    of the benchmark), by new files and new entries only."""
    data = root / "benchmarks" / "chip"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, c in TINY_CONFIGS.items():
        (data / "configs" / f"{name}.json").write_text(json.dumps(
            {"name": name, "model_type": "test", "block_size": 16, **c}))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmarks/chip/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for name, m in TINY_MIXES.items():
        (data / "traffic" / f"{name}.json").write_text(json.dumps({
            "name": name, "block": 8,
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                       "min": 8, "max": 100},
            "output": {"dist": "uniform", "min": 4, "max": 12}, **m}))
    for name, (config, mix, suffix, rule) in TINY_CELLS.items():
        (data / "cells" / f"{name}.json").write_text(json.dumps({
            "serving": {"rows": 4, "cache_len": 112, "prefill_chunk": 32,
                        "prefill_rows": 2, "pool_tokens": 480},
            "warm_in_s": 0.5,
            "programs": {"decode": "_decode_traced", "chunk": "_chunk_traced"},
            "check": {"served_tokens": 24, "per_request": 8, **rule}}))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1, "why": "test"})
        for m in bench["per_layer"]:
            if m["name"].endswith(suffix):
                m["workloads"].append(name)
    # an open-loop cell's tails and the layers that move them, as entries
    # a later change adds with such a cell
    slo = ["tiny-moe-slo"]
    bench["end_to_end"] += [
        {"name": "ttft_p95_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": slo},
        {"name": "tpot_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": slo}]
    bench["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": layer, "moves": "tpot_p95_ms", "workloads": slo}
        for n, u, layer in (("engine_step_ms.slo", "ms", "engine"),
                            ("idle_share.slo", "%", "device"))]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def copy_benchmark(dest: Path) -> Path:
    """``BENCHMARK.json`` and every file under its paths, copied."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(REPO / p, dest / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return dest
