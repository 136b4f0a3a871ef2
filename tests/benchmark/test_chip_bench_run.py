"""The chip benchmark's harness end to end, on the CPU at tiny sizes: the
loader, an architecture added as files, the refusals, the compile guard,
a run, and runs with tokens altered where the engine produces them,
which must come out not correct."""

import json
import os
import subprocess

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import drive, run, spec
from chip_bench_helpers import (QWEN2_CELL, REPO, TINY_CELLS, add_architecture,
                                add_tiny_cells, copy_benchmark)

RUN = ["python3", "benchmarks/chip/run.py"]


def _cli(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([*RUN, *args], cwd=cwd, env=env, text=True,
                          capture_output=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_every_workload_resolves_its_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def _alter_last_token(monkeypatch):
    """Every request the engine finishes hands back its last served token
    shifted by one: one altered token in each, where it is produced."""
    make = drive.make_engine

    def broken(*args, **kwargs):
        eng = make(*args, **kwargs)
        step = eng.step

        def altered(*a):
            done = step(*a)
            for req in done:
                req.output[req.generated - 1] = (
                    req.output[req.generated - 1] + 1) % 256
            return done

        eng.step = altered
        return eng

    monkeypatch.setattr(drive, "make_engine", broken)


def test_added_files_are_found_without_editing(tmp_path, monkeypatch):
    """Cells, a metric and an architecture the harness has no module for
    (Qwen2: QKV biases, no QK-norm) join a copy of the benchmark by new
    files and new ``BENCHMARK.json`` entries alone; the new architecture's
    tiny cell comes out correct, and not with a served token altered."""
    root = copy_benchmark(tmp_path)
    old_bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    add_tiny_cells(root)
    add_architecture(root)
    metric = root / "benchmarks" / "chip" / "metrics" / "tiny_count.py"
    metric.write_text("def read(run):\n    return 7.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "tiny_count.x", "unit": "n",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "out_tok_s",
                               "workloads": ["tiny-dense-batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    for key, entries in old_bench.items():
        if not (isinstance(entries, list) and isinstance(entries[0], dict)):
            assert bench[key] == entries, key
            continue
        for old in entries:
            new = next(e for e in bench[key] if e["name"] == old["name"])
            assert {k: v for k, v in new.items() if k != "workloads"} == \
                {k: v for k, v in old.items() if k != "workloads"}
            assert set(old.get("workloads", [])) <= set(
                new.get("workloads", []))
    for name, (config, mix, _, _) in TINY_CELLS.items():
        cell = spec.cell(name, root)
        assert cell.config["name"] == config and cell.mix["name"] == mix
    assert spec.cell("tiny-dense-batch", root).readers["tiny_count.x"](None) == 7.0
    for w in spec.benchmark()["workloads"]:
        assert spec.cell(w["name"], root).name == w["name"]

    cell = spec.cell(QWEN2_CELL, root)
    assert cell.arch.__name__.endswith("qwen2")
    r = run.run_cell(cell, 2 ** 31 + 21, 1.5, False, t_process=0.0)
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] and gap["value"] <= gap["limit"]
    assert r["checked"]["tokens"] >= 24
    _alter_last_token(monkeypatch)
    r = run.run_cell(spec.cell(QWEN2_CELL, root), 2 ** 31 + 21, 1.5, False,
                     t_process=0.0)
    gap = r["checks"]["mean_logit_gap"]
    assert not r["correct"] and gap["value"] > gap["limit"]


def test_refuses_a_host_without_a_tpu():
    r = _cli(REPO, "--workload", "qwen3-longdoc-batch", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert not _has_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    root = copy_benchmark(tmp_path)
    r = _cli(root, "--workload", "qwen3-longdoc-batch", "--seed", "1",
             "--seconds", "1")
    assert r.returncode != 0
    assert not _has_result(r.stdout)


def test_compile_in_the_window_fails():
    f = jax.jit(lambda x: x * 3 + 1)
    with pytest.raises(RuntimeError, match="compiled inside"):
        with drive.no_compiles("test window"):
            f(jnp.arange(5.0)).block_until_ready()
    with drive.no_compiles("test window"):   # warmed: no compile
        f(jnp.arange(5.0)).block_until_ready()


def _run(root, workload, seed, trace=False):
    return run.run_cell(spec.cell(workload, root), seed, 1.5, trace,
                        t_process=0.0)


def test_a_tiny_run_is_correct(tiny_root):
    r = _run(tiny_root, "tiny-dense-batch", 2 ** 31 + 11)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"out_tok_s", "setup_s"}
    assert r["metrics"]["out_tok_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    gap = r["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]
    # the sample takes its tokens from several requests
    assert r["checked"]["requests"] >= 3 and r["checked"]["tokens"] >= 24


def test_a_traced_tiny_run_reports_host_layers(tiny_root):
    r = _run(tiny_root, "tiny-moe-slo", 5, trace=True)
    assert r["checks"]["widest_logit_gap"]["value"] is not None
    names = set(r["metrics"])
    assert "engine_step_ms.slo" in names
    # no TPU plane in a CPU trace: the device readers stay silent
    assert not any(n.startswith(("decode_roofline", "idle_share"))
                   for n in names)


def test_an_altered_token_is_not_correct(tiny_root, monkeypatch):
    make = drive.make_engine

    def broken(*args, **kwargs):
        eng = make(*args, **kwargs)
        decode = eng._decode

        def altered(*a):
            nxt, buf, state = decode(*a)
            return (nxt + 1) % 256, buf, state   # every row's token, shifted

        eng._decode = altered
        return eng

    monkeypatch.setattr(drive, "make_engine", broken)
    r = _run(tiny_root, "tiny-dense-batch", 3)
    assert not r["correct"]
    gap = r["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_is_not_correct(tiny_root):
    """The control in the program's place, judged by the cell's own rule
    (the mean gap and its limit, as the committed cell is judged)."""
    r = run.run_cell(spec.cell("tiny-dense-batch", tiny_root), 4, 1.5,
                     False, t_process=0.0, control="fp8")
    gap = r["checks"]["mean_logit_gap"]
    assert not r["correct"]
    assert gap["value"] == r["gaps"]["control"]["mean"] > gap["limit"]
    assert r["gaps"]["program"]["mean"] <= gap["limit"]


def test_a_slow_step_is_noted(tiny_root, monkeypatch):
    """Every step over the stall threshold is listed with what the engine
    and the device held: here every step, with the threshold at 0."""
    monkeypatch.setattr(drive, "STALL_S", 0.0)
    r = _run(tiny_root, "tiny-dense-batch", 6)
    stalls = r["host"]["stalls"]
    assert len(stalls) >= r["host"]["steps"] > 0
    assert {"at_s", "ms", "prefilling", "decoding",
            "blocks_live"} <= set(stalls[0])
