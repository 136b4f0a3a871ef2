"""The trace reduction: on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e chip (``data/``)."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import trace_reduce

DATA = Path(__file__).with_name("data")
PROGRAMS = {"decode": "_decode_traced", "chunk": "_chunk_traced"}


def _hand_made():
    return {
        "host": [["bench.window", 0, 1000], ["engine.step", 100, 300],
                 ["traffic.wait", 500, 400]],
        "devices": {"0": {
            "modules": [["jit__decode_traced(11)", 150, 200],
                        ["jit__chunk_traced(12)", 600, 100],
                        ["jit__decode_traced(11)", 5000, 100]],  # outside
            "ops": [["%fusion.1 = bf16[8] fusion(...)", 150, 100],
                    ["%fusion.2 = bf16[8] fusion(...)", 260, 90],
                    ["%convolution.3 = bf16[8] convolution(...)", 600, 100]]}},
    }


def test_hand_made_trace():
    r = trace_reduce.reduce(_hand_made(), PROGRAMS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(290e-9)
    assert r["programs"]["decode"] == pytest.approx([200e-9])
    assert r["programs"]["chunk"] == pytest.approx([100e-9])
    idle = dict(r["idle_gaps"])
    assert idle["engine.step"] == pytest.approx(110e-9)
    assert idle["traffic.wait"] == pytest.approx(300e-9)
    assert idle["host outside the harness spans"] == pytest.approx(300e-9)
    assert sum(idle.values()) == pytest.approx(1000e-9 - 290e-9)
    ops = dict(r["device_ops"])
    assert ops["_decode_traced:fusion.1"] == pytest.approx(100e-9)
    assert ops["_chunk_traced:convolution.3"] == pytest.approx(100e-9)


def test_no_window_or_no_device_reads_nothing():
    ev = _hand_made()
    assert trace_reduce.reduce({**ev, "devices": {}}, PROGRAMS) is None
    ev["host"] = ev["host"][1:]
    assert trace_reduce.reduce(ev, PROGRAMS) is None


def test_recorded_v5e_trace():
    """300 ms of a traced ``qwen3-longdoc-batch`` window on one TPU v5e
    chip: one chunk and one decode execution each, op names cut to their
    short form."""
    ev = json.loads((DATA / "qwen3_longdoc_v5e_trace.json").read_text())
    r = trace_reduce.reduce(ev, PROGRAMS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert 0 < r["busy_s"] <= r["window_s"]
    mods = ev["devices"]["0"]["modules"]
    for key, fn in PROGRAMS.items():
        want = [d * 1e-9 for n, s, d in mods if n.startswith(f"jit_{fn}(")]
        assert want and r["programs"][key] == pytest.approx(want)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    # self times: no op's own time exceeds the busy time
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"]
    assert len(r["device_ops"]) == trace_reduce.TOP
