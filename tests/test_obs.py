"""Telemetry subsystem (DESIGN.md §15): profiler-backed spans (nesting,
late arguments, rank attribution under pool threads, the Perfetto
export), the engine's step phases on the profiler's clock and in
``last_step_phases``, the model's named scopes in the decode program,
the residual ledger + serialization-stall detector, the metrics
registry, trial-flush wiring on engine reset / fabric close, and the
inert-when-disabled contract (no profiler session: a span records
nothing; no ledger: one ``None`` check)."""

import glob
import gzip
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import ServeConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.obs import metrics as M
from repro.obs import residuals as R
from repro.obs import trace as T
from repro.serve import ContinuousEngine, ServingFabric

PHASES = ("serve.admit", "serve.chunk.build", "serve.chunk.dispatch",
          "serve.chunk.sync", "serve.chunk.retire", "serve.decode.dispatch",
          "serve.decode.sync", "serve.decode.retire")


@pytest.fixture
def ledger():
    led = T.install()
    M.install()
    yield led
    T.uninstall()
    M.uninstall()


@pytest.fixture
def off():
    """Force the disabled state (REPRO_TRACE=1 in the environment
    auto-installs at import)."""
    T.uninstall()
    M.uninstall()
    yield


@pytest.fixture(scope="module")
def bundle():
    cfg = get_smoke_config("gemma-2b")
    train = TrainConfig(param_dtype="float32", compute_dtype="float32",
                        loss_chunk=16, attn_chunk_threshold=64,
                        attn_chunk=16, remat=False)
    model = build_model(cfg, train, ServeConfig(), tp=1)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _paged_engine(bundle, **kw):
    cfg, model, params = bundle
    return ContinuousEngine(model, params, cache_len=48, num_slots=2,
                            prefill_chunk=16, kv_layout="paged",
                            block_size=8, **kw)


def _host_spans(trace_dir, prefixes=("serve.", "fabric.", "comm.", "t.")):
    """``(name, start_ns, end_ns, args, thread)`` of the profile's host
    spans whose names start with ``prefixes``, in start order."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    out = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), thread) for e in line.events
                       if e.name.startswith(prefixes))
    return sorted(out, key=lambda s: s[1])


def _within(inner, outer):
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def test_span_nesting_parent_recorded(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with T.span("t.outer", k=1):
            with T.span("t.inner", k=2):
                time.sleep(0.001)
    spans = {s[0]: s for s in _host_spans(tmp_path)}
    outer, inner = spans["t.outer"], spans["t.inner"]
    assert _within(inner, outer)
    assert inner[2] - inner[1] >= 1e6
    assert outer[3] == {"k": 1} and inner[3] == {"k": 2}


def test_span_phases_accumulate_by_name():
    phases = {}
    for _ in range(3):
        with T.span("t.x", phases) as sp:
            time.sleep(0.001)
    with T.span("t.y", phases):
        pass
    assert set(phases) == {"t.x", "t.y"}
    assert phases["t.x"] >= 3e-3
    assert sp.seconds >= 1e-3 and phases["t.x"] >= sp.seconds


def test_manual_enter_end_records_once(tmp_path):
    """The CommStream shape: entered in ``__enter__``, ended in
    ``__exit__`` of another object."""
    with jax.profiler.trace(str(tmp_path)):
        sp = T.span("t.manual", stream="decode")
        sp.__enter__()
        sp.end()
    spans = [s for s in _host_spans(tmp_path) if s[0] == "t.manual"]
    assert len(spans) == 1 and spans[0][3] == {"stream": "decode"}


def test_span_exception_ends_and_prices_nothing(ledger, tmp_path):
    phases = {}
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(RuntimeError):
            with T.span("t.fails", phases) as sp:
                sp.hop("migration", 1e-3)
                raise RuntimeError("mid-hop")
    assert ledger.counts() == {}
    assert "t.fails" in phases
    (span,) = [s for s in _host_spans(tmp_path) if s[0] == "t.fails"]
    assert "hop" not in span[3]


def test_rank_attribution_under_pool_threads(ledger, tmp_path):
    """Fabric shape: a ThreadPoolExecutor re-assigns threads to ranks
    arbitrarily per step; the rank-step span's ``rank=`` must tag the
    span and every priced hop inside it, with no cross-thread leak."""
    seen = []

    def one_step(rank, step):
        with T.span("t.rank_step", rank=rank, step=step):
            with T.span("t.sub") as sp:
                sp.hop("admission", 1e-3)
                seen.append((rank, T.current_rank()))
                time.sleep(0.0005)
        assert T.current_rank() is None

    with jax.profiler.trace(str(tmp_path)):
        with ThreadPoolExecutor(max_workers=3,
                                thread_name_prefix="fabric-rank") as ex:
            for f in [ex.submit(one_step, rank, step)
                      for step in range(8) for rank in range(4)]:
                f.result()
    assert all(rank == cur for rank, cur in seen)
    steps = [s for s in _host_spans(tmp_path) if s[0] == "t.rank_step"]
    subs = [s for s in _host_spans(tmp_path) if s[0] == "t.sub"]
    assert len(steps) == len(subs) == 32
    assert sorted(s[3]["rank"] for s in steps) == sorted(list(range(4)) * 8)
    for sub in subs:              # each sub nests in one rank step
        assert sum(_within(sub, st) for st in steps) == 1
    with ledger._lock:
        ranks = sorted(r for _, _, r in ledger._hops["admission"])
    assert ranks == sorted(list(range(4)) * 8)


def test_no_rank_outside_rank_span(ledger):
    assert T.current_rank() is None
    with T.span("t.router") as sp:
        sp.hop("router_dispatch", 1e-4)
    with ledger._lock:
        assert ledger._hops["router_dispatch"][0][2] == -1


def test_profile_keeps_every_span(tmp_path):
    """No ring: every span of a long session is in the profile."""
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3000):
            with T.span("t.many", i=i):
                pass
    spans = [s for s in _host_spans(tmp_path) if s[0] == "t.many"]
    assert [s[3]["i"] for s in spans] == list(range(3000))


def test_late_arguments_land_on_the_span(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with T.span("t.late", rid=3) as sp:
            sp.set(prefix_hit_tokens=16, finished=2)
    (span,) = [s for s in _host_spans(tmp_path) if s[0] == "t.late"]
    assert span[3] == {"rid": 3, "prefix_hit_tokens": 16, "finished": 2}


def test_chrome_trace_valid_json(tmp_path):
    """``--trace-out``'s export: the profiler's Perfetto trace parses as
    trace_event JSON with each span's arguments."""
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        with T.span("t.rank_step", rank=1, role="full"):
            with T.span("t.decode", rows=2):
                pass
    (path,) = glob.glob(f"{tmp_path}/**/perfetto_trace.json.gz",
                        recursive=True)
    doc = json.loads(gzip.open(path).read())
    evs = {e["name"]: e for e in doc["traceEvents"]
           if e.get("name", "").startswith("t.")}
    assert evs["t.rank_step"]["ph"] == "X"
    assert evs["t.rank_step"]["args"] == {"rank": "1", "role": "full"}
    assert evs["t.decode"]["args"] == {"rows": "2"}
    assert evs["t.decode"]["dur"] <= evs["t.rank_step"]["dur"]


def test_hop_emits_span_and_residual(ledger, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with T.span("t.rank_step", rank=2):
            with T.span("t.migrate", rid=7) as sp:
                time.sleep(0.001)
                sp.hop("migration", 0.5e-3)
    (span,) = [s for s in _host_spans(tmp_path) if s[0] == "t.migrate"]
    args = span[3]
    assert args["hop"] == "migration" and args["rid"] == 7
    assert args["modeled_s"] == pytest.approx(0.5e-3)
    assert args["measured_s"] >= 1e-3
    rep = ledger.report()
    assert rep["hops"]["migration"]["n"] == 1
    assert rep["hops"]["migration"]["measured_s"] == pytest.approx(
        args["measured_s"])
    with ledger._lock:
        assert ledger._hops["migration"][0][2] == 2


# ---------------------------------------------------------------------------
# engine phases and model scopes
# ---------------------------------------------------------------------------

def _prompt(cfg, n):
    return np.arange(n, dtype=np.int32)[None] % cfg.vocab_size


def test_engine_phases_in_profile(bundle, tmp_path):
    """A profiled paged engine: every phase nests in a ``serve.step`` on
    the same thread, admission carries the request, the step its rows."""
    cfg = bundle[0]
    eng = _paged_engine(bundle)
    eng.generate({"tokens": _prompt(cfg, 24)}, 3)          # compile
    eng.reset()
    with jax.profiler.trace(str(tmp_path)):
        eng.generate({"tokens": np.concatenate([_prompt(cfg, 24)] * 2)}, 4)
    spans = _host_spans(tmp_path, ("serve.",))
    steps = [s for s in spans if s[0] == "serve.step"]
    assert steps and {"rows", "prefilling", "decoding", "blocks_free",
                      "deferred", "finished"} <= set(steps[0][3])
    assert max(s[3]["rows"] for s in steps) == 2
    names = {s[0] for s in spans}
    assert set(PHASES) <= names
    for sp in spans:
        if sp[0] != "serve.step":
            assert sum(_within(sp, st) for st in steps) == 1, sp[0]
    admits = [s[3] for s in spans if s[0] == "serve.admit"]
    assert sorted(a["rid"] for a in admits) == [0, 1]
    assert all(a["prompt_len"] == 24 and a["prefix_hit_tokens"] == 0
               and a["waited_s"] >= 0 for a in admits)


def test_decode_dispatch_counts_pages(bundle, tmp_path):
    """``serve.decode.dispatch`` carries what decode attention reads,
    from the host's lengths: each decoding row's pages up to the token it
    decodes, against the whole tables' width, and the path (the gather
    on a CPU)."""
    from repro.serve import ServeRequest
    cfg = bundle[0]
    eng = _paged_engine(bundle)
    assert eng.attn_path == "gather"
    eng.generate({"tokens": _prompt(cfg, 20)}, 3)           # compile
    eng.reset()
    with jax.profiler.trace(str(tmp_path)):
        for rid, n in enumerate((20, 9)):
            eng.submit(ServeRequest(rid=rid, batch={"tokens": _prompt(cfg, n)},
                                    max_new_tokens=4), 0.0)
        while not eng.idle:
            eng.step(0.0)
    spans = [s[3] for s in _host_spans(tmp_path, ("serve.decode.dispatch",))]
    assert spans
    table = 2 * 6                          # 2 rows of 48 // 8 entries
    assert all(a["table_pages"] == table and a["attn_path"] == "gather"
               for a in spans)
    # the 20-token prompt decodes at positions 20..22 (3 pages of 8), the
    # 9-token one at 9..11 (2 pages); alone or together
    assert {a["kv_pages"] for a in spans} <= {2, 3, 5}
    assert 5 in {a["kv_pages"] for a in spans}


def test_decode_pages_follow_lengths(bundle):
    eng = _paged_engine(bundle)
    from repro.serve import ServeRequest
    eng.submit(ServeRequest(rid=0, batch={"tokens": _prompt(bundle[0], 14)},
                            max_new_tokens=6), 0.0)
    seen = []
    while not eng.idle:
        pages = eng._decode_pages()
        if eng.num_decoding:
            slot = eng.kv.live_slots[0]
            seen.append((eng.kv.length(slot), pages["kv_pages"]))
        eng.step(0.0)
    # a row decoding the token at position n reads ceil((n + 1) / 8) pages
    assert seen and all(p == -(-(n + 1) // 8) for n, p in seen)
    assert {p for _, p in seen} == {2, 3}


def test_last_step_phases_without_profiler(bundle):
    cfg = bundle[0]
    eng = _paged_engine(bundle)
    req_tokens = _prompt(cfg, 20)
    from repro.serve import ServeRequest
    eng.submit(ServeRequest(rid=0, batch={"tokens": req_tokens},
                            max_new_tokens=4), 0.0)
    eng.step(0.0)                        # admit + first chunk
    first = eng.last_step_phases
    assert {"serve.step", "serve.admit", "serve.chunk.build",
            "serve.chunk.dispatch", "serve.chunk.retire"} <= set(first)
    eng.step(0.0)                        # last chunk, then a decode
    phases = eng.last_step_phases
    assert phases is not first
    assert {"serve.chunk.sync", "serve.decode.dispatch",
            "serve.decode.sync", "serve.decode.retire"} <= set(phases)
    inner = sum(v for k, v in phases.items() if k != "serve.step")
    assert 0 < inner <= phases["serve.step"]


def test_decode_program_ops_carry_scopes(bundle):
    """The paged programs' optimized HLO names each op's scope in its
    ``op_name`` metadata: the map a profile's device ops are read by."""
    eng = _paged_engine(bundle)
    eng.generate({"tokens": _prompt(bundle[0], 20)}, 2)
    counts = eng.prefill_compiles, eng.decode_compiles
    text = eng.program_text()
    assert (eng.prefill_compiles, eng.decode_compiles) == counts
    assert set(text) == {"_decode_traced", "_chunk_traced"}
    for fn, hlo in text.items():
        for scope in ("attention", "mlp", "norm", "lm_head", "sample",
                      "layers"):
            assert f"/{scope}/" in hlo, (fn, scope)


@pytest.mark.parametrize("placement", ["replicated", "disagg"])
def test_fabric_spans_in_profile(ledger, bundle, tmp_path, placement):
    """The router's dispatch and migrate hops, each rank's step (with
    ``rank=``) and the lockstep join are spans; engine steps nest in
    their rank's step."""
    from repro.serve import ServeRequest
    cfg, model, params = bundle
    fab = ServingFabric(model, params, ranks=2, placement=placement,
                        cache_len=48, slots_per_rank=2, prefill_chunk=16,
                        block_size=8)
    try:
        for rid in range(4):
            fab.submit(ServeRequest(rid=rid, batch={"tokens": _prompt(
                cfg, 20 + rid)}, max_new_tokens=3), 0.0)
        with jax.profiler.trace(str(tmp_path)):
            while not fab.idle:
                fab.step(0.0)
    finally:
        fab.close()
    spans = _host_spans(tmp_path)
    ranks = [s for s in spans if s[0] == "fabric.rank_step"]
    assert sorted({s[3]["rank"] for s in ranks}) == [0, 1]
    assert any(s[0] == "fabric.step" for s in spans)
    hops = {s[3]["hop"] for s in spans if "hop" in s[3]}
    want = {"router_dispatch", "admission"}
    if placement == "disagg":
        want.add("migration")
        assert any(s[0] == "fabric.kv_transfer" for s in spans)
    assert want <= hops
    for s in spans:
        if "hop" in s[3]:
            assert {"modeled_s", "measured_s"} <= set(s[3])
    for st in (s for s in spans if s[0] == "serve.step"):
        assert sum(_within(st, r) for r in ranks) == 1


# ---------------------------------------------------------------------------
# residual ledger + serialization-stall detector
# ---------------------------------------------------------------------------

def test_residual_report_flags_over_factor():
    led = R.ResidualLedger()
    led.record("admission", 1e-3, 1.1e-3)         # on-model
    led.record("migration", 1e-3, 5e-3, rank=1)   # 5x over
    rep = led.report(factor=2.0)
    assert rep["hops"]["admission"]["ratio"] == pytest.approx(1.1)
    assert rep["hops"]["migration"]["ratio"] == pytest.approx(5.0)
    assert rep["flagged"] == ["migration"]
    assert rep["hops"]["migration"]["n_off"] == 1
    assert rep["hops"]["migration"]["worst_over"] == pytest.approx(5.0)


def test_residual_unmodeled_hop_is_inf():
    led = R.ResidualLedger()
    led.record("router_dispatch", 0.0, 1e-4)
    rep = led.report()
    assert rep["hops"]["router_dispatch"]["ratio"] == float("inf")
    assert "router_dispatch" in rep["flagged"]


def test_residual_under_factor_flagged_too():
    led = R.ResidualLedger()
    led.record("spec_verify", 1e-2, 1e-3)         # 10x under
    assert led.report()["flagged"] == ["spec_verify"]


def test_stall_detector_gated_on_runnable(ledger):
    def waits():
        T.set_runnable(0)
        T.on_wait(2e-3)                           # no runnable work: idle
        assert ledger.report()["serialization_stall_s"] == 0.0
        T.set_runnable(3)
        with T.span("t.rank_step", rank=1):
            T.on_wait(2e-3)                       # blocked while runnable

    th = threading.Thread(target=waits)
    th.start()
    th.join()
    rep = ledger.report()
    assert rep["serialization_stall_s"] == pytest.approx(2e-3)
    assert rep["stall_events"] == 1
    assert rep["stall_by_rank"] == {"1": pytest.approx(2e-3)}


def test_merge_reports_recombines_sums():
    a, b = R.ResidualLedger(), R.ResidualLedger()
    a.record("admission", 1e-3, 2e-3)
    a.stall(1e-3, rank=0)
    b.record("admission", 1e-3, 4e-3)
    b.record("migration", 2e-3, 2e-3, rank=1)
    b.stall(2e-3, rank=0)
    merged = R.merge_reports([a.report(), b.report(), {}])
    assert merged["hops"]["admission"]["n"] == 2
    assert merged["hops"]["admission"]["ratio"] == pytest.approx(3.0)
    assert merged["hops"]["migration"]["ratio"] == pytest.approx(1.0)
    assert merged["flagged"] == ["admission"]
    assert merged["serialization_stall_s"] == pytest.approx(3e-3)
    assert merged["stall_by_rank"]["0"] == pytest.approx(3e-3)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram(ledger):
    reg = M.active()
    reg.counter("sched.admitted").inc(3)
    reg.counter("sched.admitted").inc()
    reg.gauge("sched.queue_depth").set(7)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("latency_s").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["sched.admitted"] == 4.0
    assert snap["gauges"]["sched.queue_depth"] == 7.0
    h = snap["histograms"]["latency_s"]
    assert h["count"] == 4.0 and h["mean"] == pytest.approx(2.5)
    assert h["min"] == 1.0 and h["max"] == 4.0
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_snapshot_merges_registry_and_extra(ledger):
    M.active().counter("tokens_out").inc(5)
    out = M.snapshot(extra={"tok_s": 12.0})
    assert out["tok_s"] == 12.0
    assert out["metrics"]["counters"]["tokens_out"] == 5.0


# ---------------------------------------------------------------------------
# trial-flush wiring (the PR 5 req_log aliasing class)
# ---------------------------------------------------------------------------

def test_engine_reset_flushes_trial(ledger, bundle):
    eng = _paged_engine(bundle)
    ledger.record("admission", 1e-3, 5e-3)        # warm-up pollution
    M.active().counter("tokens_out").inc(9)
    eng.reset()
    assert ledger.counts() == {}
    assert M.active().snapshot()["counters"] == {}


def test_engine_reset_preserve_prefix_flushes_too(ledger, bundle):
    eng = _paged_engine(bundle, prefix_cache=True)
    ledger.record("prefix_hit", 1e-3, 1e-3)
    eng.reset(preserve_prefix=True)
    assert ledger.counts() == {}


def test_fabric_close_flushes_trial(ledger, bundle):
    cfg, model, params = bundle
    fab = ServingFabric(model, params, ranks=2, placement="replicated",
                        cache_len=32, slots_per_rank=2, prefill_chunk=16,
                        block_size=8)
    ledger.record("router_dispatch", 1e-4, 1e-4)
    fab.close()
    assert ledger.counts() == {}
    assert fab.scheduler.req_log == {}
    assert fab.total_steps == 0


def test_fabric_speculate_requires_replicated(bundle):
    cfg, model, params = bundle
    with pytest.raises(ValueError, match="disaggregated"):
        ServingFabric(model, params, ranks=2, placement="disagg",
                      cache_len=32, slots_per_rank=2, prefill_chunk=16,
                      block_size=8, speculate=2)


# ---------------------------------------------------------------------------
# inert when disabled (the <2% overhead contract, structurally)
# ---------------------------------------------------------------------------

def test_disabled_hooks_inert(off):
    assert T.ledger() is None
    assert M.active() is None
    T.flush_trial()                     # no-ops, no error
    M.flush_trial()
    T.on_wait(1.0)
    with T.span("t.unpriced") as sp:
        sp.hop("admission", 1e-3)       # no ledger: nothing to record


def test_disabled_guard_is_one_global_read(off):
    """The ledger's sites when telemetry is off: one module-global read
    plus a None check. Bound it generously — the point is that nothing
    allocates or reads the clock on the disabled path."""
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        led = T.ledger()
        if led is not None:             # pragma: no cover
            led.record("never", 0.0, 0.0)
    dt = time.perf_counter() - t0
    assert dt / n < 5e-6                # < 5us per guard, vastly above cost


def test_span_without_profiler_is_cheap(off):
    """No profiler session: a span is one annotation that records
    nothing plus two clock reads. Bound it generously — the point is that
    nothing is kept."""
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with T.span("t.off", rows=3):
            pass
    dt = time.perf_counter() - t0
    assert dt / n < 20e-6


def test_install_is_fresh_each_time():
    led1 = T.install()
    led1.record("admission", 1e-3, 1e-3)
    led2 = T.install()
    try:
        assert T.ledger() is led2
        assert led2.counts() == {}
    finally:
        T.uninstall()
