"""One cell through the program's paged ``ContinuousEngine``.

Builds the model from the configuration file, draws its weights, warms
up the engine's own programs, then runs the traffic through
``submit``/``step`` on one host thread: warm-in, then the measured
window. The loop is the serve driver's ``_drive_wall_clock`` adapted to a
window: requests are submitted when due (or, for a backlog, whenever the
queue runs short), and every request's progress is read after each step.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from benchmarks.chip import e2e, traffic, weights as weights_mod

#: program config field for each published key of a configuration file
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
           "num_experts": "num_experts", "num_experts_per_tok": "top_k",
           "tie_word_embeddings": "tie_embeddings"}


def program_model(config: dict):
    """The program's model for a configuration file, in bfloat16."""
    from repro.config import ServeConfig, TrainConfig
    from repro.configs import get_config
    from repro.models.registry import build_model

    c = dict(config["config"])
    if c.get("num_experts") and not c.get("norm_topk_prob", True):
        raise ValueError("the program always renormalises the top-k gates; "
                         "a configuration with norm_topk_prob false cannot "
                         "run as stated")
    c.setdefault("head_dim", c["hidden_size"] // c["num_attention_heads"])
    base = get_config(config["arch"])
    cfg = dataclasses.replace(base, **{
        f: type(getattr(base, f))(c[k]) for k, f in _FIELDS.items() if k in c})
    tcfg = TrainConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                       remat=False)
    return build_model(cfg, tcfg, ServeConfig(), tp=1)


def make_engine(model, params, config: dict, serving: dict):
    from repro.serve import ContinuousEngine
    bs = int(config["block_size"])
    return ContinuousEngine(
        model, params, cache_len=int(serving["cache_len"]),
        num_slots=int(serving["rows"]),
        prefill_chunk=int(serving["prefill_chunk"]),
        max_prefill_per_step=int(serving["prefill_rows"]),
        kv_layout="paged", block_size=bs,
        num_blocks=int(serving["pool_tokens"]) // bs)


def warm_up(eng, vocab: int) -> None:
    """Compile the engine's chunk, decode and retire programs: the shapes
    are fixed by the engine's settings, so one short request covers
    every one of them. Then clear the engine."""
    toks = np.arange(eng.prefill_chunk + 1, dtype=np.int32)[None] % vocab
    eng.generate({"tokens": toks}, 3)
    eng.reset()


@contextlib.contextmanager
def no_compiles(what: str):
    """Raise if JAX compiled (or loaded from its cache) any program inside
    the block: the measured window runs on warmed programs only."""
    seen = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    if seen:
        raise RuntimeError(f"{len(seen)} program(s) compiled inside the "
                           f"{what} ({sum(seen):.1f} s)")


class GcPauses:
    """Collects the duration of each garbage-collector pass while active."""

    def __init__(self):
        self.seconds: List[float] = []
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


#: a step at least this long (s) is a stall: the run notes what it held
STALL_S = 1.0
_MEMORY_KEYS = ("bytes_in_use", "largest_free_block_bytes", "num_allocs",
                "bytes_reserved")


@dataclass
class Served:
    """A finished request as the check needs it."""
    rid: int
    prompt: np.ndarray
    served: np.ndarray


@dataclass
class StepWork:
    """What one engine step dispatched, read from request progress."""
    t0: float
    t1: float
    chunk_rows: list            # (pos0, n) per prompt chunk
    decode_ctx: list            # keys seen by each decoded row


@dataclass
class Driver:
    """The traffic loop around one engine."""
    eng: object
    mix: dict
    seed: int
    vocab: int
    clock: Callable[[], float] = time.perf_counter
    annotate: Optional[Callable] = None      # host span context, traced runs
    records: List[e2e.Record] = field(default_factory=list)
    finished: List[Served] = field(default_factory=list)
    steps: List[tuple] = field(default_factory=list)
    work: List[StepWork] = field(default_factory=list)
    record_work: bool = False
    peak_blocks: int = 0
    stalls: List[dict] = field(default_factory=list)

    def __post_init__(self):
        self._draws = traffic.draws(self.mix, self.seed)
        self._next = next(self._draws)
        self._origin = None
        self._due = 0.0
        self._live: Dict[int, tuple] = {}
        arrival = self.mix["arrival"]
        self._backlog = (int(arrival["depth"]) + self.eng.kv.num_slots
                         if arrival["kind"] == "backlog" else 0)

    def _span(self, name):
        return self.annotate(name) if self.annotate else contextlib.nullcontext()

    def _submit(self, draw: traffic.Draw, due: float, now: float) -> None:
        from repro.serve.scheduler import ServeRequest
        tokens = traffic.prompt_tokens(self.seed, draw.rid, draw.prompt_len,
                                       self.vocab)
        req = ServeRequest(rid=draw.rid, batch={"tokens": tokens},
                           max_new_tokens=draw.max_new, temperature=0.0,
                           seed=self.seed & 0x7FFFFFFF, arrival=due)
        rec = e2e.Record(rid=draw.rid, prompt_len=draw.prompt_len,
                         max_new=draw.max_new, due=due)
        self.records.append(rec)
        try:
            self.eng.submit(req, now)
        except ValueError:
            rec.failed = True
            return
        self._live[draw.rid] = (req, rec)

    def _submit_due(self, now: float) -> None:
        if self._backlog:
            while len(self._live) < self._backlog:
                self._submit(self._next, now, now)
                self._next = next(self._draws)
            return
        while self._due <= now:
            self._submit(self._next, self._due, now)
            self._next = next(self._draws)
            self._due += self._next.gap_s

    def _observe(self, t: float, done) -> None:
        for req, rec in self._live.values():
            rec.generated = req.generated
            if rec.first is None and req.generated > 0:
                rec.first = t
        for req in done:
            _, rec = self._live.pop(req.rid)
            rec.finish = t
            self.finished.append(Served(
                rid=req.rid, prompt=np.asarray(req.batch["tokens"][0]),
                served=np.asarray(req.output[:req.generated])))

    def _step_work(self, before, t0: float, t1: float) -> StepWork:
        C = self.eng.prefill_chunk
        chunk_rows, decode_ctx = [], []
        for req, g0, c0 in before:
            g1, c1, plen = req.generated, req.prefill_chunks, req.prompt_len
            decoded = g1 - g0
            if c1 > c0:
                pos0 = c0 * C
                chunk_rows.append((pos0, min(plen, c1 * C) - pos0))
                if c1 * C >= plen and g1 > g0:
                    decoded -= 1          # the first token came from the chunk
            if decoded > 0:
                decode_ctx.append(plen + g1 - 1)
        return StepWork(t0, t1, chunk_rows, decode_ctx)

    def start(self) -> None:
        """Open the traffic: the first request is due now."""
        self._origin = self.clock()
        self._due = self._origin

    def run_until(self, t_end: float) -> float:
        """Serve until the first step boundary at or after ``t_end``;
        returns that time."""
        eng = self.eng
        pool = eng.kv.pool
        while True:
            now = self.clock()
            if now >= t_end:
                return now
            with self._span("traffic.submit"):
                self._submit_due(now)
            if eng.idle:
                with self._span("traffic.wait"):
                    time.sleep(max(0.0, min(self._due, t_end) - now))
                continue
            before = ([(req, req.generated, req.prefill_chunks)
                       for req, _ in self._live.values()]
                      if self.record_work else None)
            t0 = self.clock()
            with self._span("engine.step"):
                done = eng.step(t0)
            t1 = self.clock()
            self.steps.append((t0, t1))
            if t1 - t0 >= STALL_S:
                self._note_stall(t0, t1)
            self._observe(t1, done)
            self.peak_blocks = max(self.peak_blocks, pool.num_live)
            if before is not None:
                self.work.append(self._step_work(before, t0, t1))

    def _note_stall(self, t0: float, t1: float) -> None:
        """What the engine and the device held around a step that took
        ``STALL_S`` or more: where in the run, how many rows prefilled and
        decoded, the pool's leased blocks, and the device's allocator."""
        eng = self.eng
        mem = jax.devices()[0].memory_stats() or {}
        self.stalls.append({
            "at_s": round(t0 - self._origin, 3), "ms": 1e3 * (t1 - t0),
            "prefilling": eng.num_prefilling,
            "decoding": eng.num_decoding,
            "blocks_live": eng.kv.pool.num_live,
            **{k: mem[k] for k in _MEMORY_KEYS if k in mem}})

    def host_stats(self, t0: float, t1: float) -> dict:
        """The longest step and the longest wait between two steps in
        [t0, t1], in ms: where a stall of the host shows."""
        steps = [(a, b) for a, b in self.steps if t0 <= a and b <= t1]
        gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
        return {"steps": len(steps),
                "longest_step_ms": 1e3 * max((b - a for a, b in steps),
                                             default=0.0),
                "longest_between_steps_ms": 1e3 * max(gaps, default=0.0),
                "stalls": self.stalls}

    def mark_window_start(self) -> None:
        for rec in self.records:
            rec.gen_at_start = rec.generated
        self.peak_blocks = self.eng.kv.pool.num_live

    def mark_window_end(self) -> None:
        for rec in self.records:
            rec.gen_at_end = rec.generated


def draw_weights(model, seed: int):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return weights_mod.draw(shapes, seed)
