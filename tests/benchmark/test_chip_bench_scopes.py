"""The reduction by program span and model scope (``trace_scopes.py``)
and its readers: on a hand-made trace with known answers, on the small
trace recorded on a TPU v5e chip (``data/``), which has neither and must
reduce exactly as before, and through a traced tiny cell on the CPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.chip import spec, trace_reduce, trace_scopes

DATA = Path(__file__).with_name("data")
PROGRAMS = {"decode": "_decode_traced", "chunk": "_chunk_traced"}

HLO = """
%body (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(_decode_traced)/layers/while/body/closed_call/attention/dot_general" stack_frame_id=3}
  ROOT %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.4, metadata={op_name="jit(_decode_traced)/layers/while/body/dynamic_slice"}
}
ENTRY %main.2 (x: f32[8]) -> s32[] {
  %copy.3 = f32[8]{0} copy(%x)
  %fusion.2 = (f32[8]{0}, s32[]) fusion(%x), kind=kOutput, calls=%fc.2, metadata={op_name="jit(_decode_traced)/sample/sample/vmap()/max"}
  ROOT %fusion.5 = s32[] fusion(%x), metadata={op_name="jit(_decode_traced)/iota"}
}
"""


def test_op_scopes_from_optimized_hlo():
    assert trace_scopes.op_scopes(HLO) == {
        "fusion.1": "attention", "fusion.4": "layers", "copy.3": "unscoped",
        "fusion.2": "sample", "fusion.5": "unscoped"}


def _hand_made():
    """A window of 1000 ns: two steps, one chunk and two decode
    executions, every op's scope known."""
    step1 = [["serve.step", 100, 500], ["serve.chunk.build", 110, 90],
             ["serve.chunk.dispatch", 200, 50],
             ["serve.decode.dispatch", 300, 20],
             ["serve.decode.sync", 320, 180],
             ["serve.decode.retire", 500, 90]]
    step2 = [["serve.step", 700, 200], ["serve.decode.dispatch", 710, 10],
             ["serve.decode.sync", 720, 160],
             ["serve.decode.retire", 880, 15]]
    return {
        "host": [["bench.window", 0, 1000], ["engine.step", 100, 500],
                 ["engine.step", 700, 200]],
        "spans": [s + [0] for s in step1 + step2],
        "op_scopes": {"_decode_traced": {"fusion.1": "attention",
                                         "fusion.2": "sample"},
                      "_chunk_traced": {"fusion.9": "mlp"}},
        "devices": {"0": {
            "modules": [["jit__chunk_traced(2)", 250, 50],
                        ["jit__decode_traced(1)", 300, 180],
                        ["jit__decode_traced(1)", 720, 150]],
            "ops": [["%fusion.9 = bf16[8] fusion(...)", 250, 50],
                    ["%fusion.1 = bf16[8] fusion(...)", 300, 100],
                    ["%fusion.2 = bf16[8] fusion(...)", 400, 50],
                    ["%copy.3 = bf16[8] copy(...)", 450, 30],
                    ["%fusion.1 = bf16[8] fusion(...)", 720, 100],
                    ["%fusion.2 = bf16[8] fusion(...)", 820, 50]]}},
    }


def test_hand_made_trace_by_span_and_scope():
    ev = _hand_made()
    r = trace_scopes.reduce(ev, PROGRAMS)
    old = trace_reduce.reduce(ev, PROGRAMS)
    assert {k: r[k] for k in old} == old
    assert r["scopes"]["decode"] == pytest.approx(
        {"attention": 100e-9, "sample": 50e-9, "unmapped": 15e-9})
    assert r["scopes"]["chunk"] == pytest.approx({"mlp": 50e-9})
    assert r["phases"] == [
        pytest.approx({"serve.step": 500e-9, "serve.chunk.build": 90e-9,
                       "serve.chunk.dispatch": 50e-9,
                       "serve.decode.dispatch": 20e-9,
                       "serve.decode.sync": 180e-9,
                       "serve.decode.retire": 90e-9}),
        pytest.approx({"serve.step": 200e-9, "serve.decode.dispatch": 10e-9,
                       "serve.decode.sync": 160e-9,
                       "serve.decode.retire": 15e-9})]
    idle = dict(r["idle_phases"])
    assert idle == pytest.approx({
        trace_scopes.OUTSIDE: 300e-9, "serve.step": 35e-9,
        "serve.chunk.build": 90e-9, "serve.chunk.dispatch": 50e-9,
        "serve.decode.sync": 30e-9, "serve.decode.retire": 105e-9,
        "serve.decode.dispatch": 10e-9})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_spans_on_other_threads_stay_out_of_a_step():
    ev = _hand_made()
    ev["spans"].append(["serve.decode.sync", 150, 100, 1])
    phases = trace_scopes.reduce(ev, PROGRAMS)["phases"]
    assert phases[0]["serve.decode.sync"] == pytest.approx(180e-9)


def test_recorded_v5e_trace_reduces_as_before():
    """The recorded trace has no program spans and no scope maps: the
    reduction is exactly the old one, with or without empty ones."""
    ev = json.loads((DATA / "qwen3_longdoc_v5e_trace.json").read_text())
    old = trace_reduce.reduce(ev, PROGRAMS)
    assert trace_scopes.reduce(ev, PROGRAMS) == old
    assert trace_scopes.reduce({**ev, "spans": [], "op_scopes": {}},
                               PROGRAMS) == old


def _view(trace):
    return SimpleNamespace(trace=trace)


@pytest.mark.parametrize("name, want", [
    ("sample_dev_ms", 50e-6), ("attn_dev_ms", 100e-6),
    ("step_host_ms", 180e-6)])
def test_readers_on_a_reduced_trace(name, want):
    r = trace_scopes.reduce(_hand_made(), PROGRAMS)
    assert spec.reader(name)(_view(r)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["sample_dev_ms", "attn_dev_ms",
                                  "step_host_ms"])
def test_readers_silent_without_spans_or_scopes(name):
    """The parent program has neither: its traced run reads nothing."""
    ev = json.loads((DATA / "qwen3_longdoc_v5e_trace.json").read_text())
    read = spec.reader(name)
    assert read(_view(None)) is None
    assert read(_view(trace_reduce.reduce(ev, PROGRAMS))) is None


def test_a_traced_tiny_window(tiny_root, tmp_path):
    """The script's path on a tiny cell, on the CPU: the program's spans
    and the scope maps of both programs are read; with no TPU plane the
    device readings stay silent and the host readings come from the
    harness's own clock."""
    cell = spec.cell("tiny-dense-batch", tiny_root)
    out = trace_scopes.traced_window(cell, 2 ** 31 + 5, 1.0,
                                     trace_dir=tmp_path / "trace")
    assert out["traced"]["spans"] > 0
    assert set(out["traced"]["scoped_ops"]) == set(PROGRAMS.values())
    assert all(n > 0 for n in out["traced"]["scoped_ops"].values())
    assert out["readings"]["sample_dev_ms"] is None
    assert out["reduced"] == {} and out["phases"] is None
