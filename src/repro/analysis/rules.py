"""Repo-specific AST lint rules (DESIGN.md §11).

Six rules, each enforcing an invariant the generic linters cannot see
because it lives in this repo's conventions (drop-mode scatters over
parked slots, carried-state threading, jit donation, Request
lifecycles, MPIX-stream regions, host/device sync discipline):

* ``scatter-drop``   — slot/block-table-indexed ``.at[...]`` writes must
  carry explicit ``mode="drop"``.
* ``state-thread``   — ``.at[...]`` writes into carried-state leaves
  (``conv``/``ssm``/``cross_k``/``cross_v`` — DESIGN.md §13) must carry
  explicit ``mode="drop"``, whatever the index is named.
* ``donated-use``    — a buffer passed through a ``donate_argnums`` jit
  must not be read again before it is rebound.
* ``request-leak``   — every issued ``Request`` must reach
  ``wait``/``test``/``waitall`` on every path (including the exception
  path of a try/finally).
* ``stream-order``   — no blocking collective inside a
  ``with comm.stream(...)`` region; no comm op on a comm after
  ``finish()``/``free()`` without a revalidating ``start()``.
* ``host-sync``      — no host-synchronizing call (``.item()``,
  ``np.asarray`` of a traced value, ``float()`` of a parameter, ...)
  inside a jit'd micro-step body.

The rules are deliberately heuristic (name patterns, function-local
dataflow): they are tuned to produce zero false positives on this tree
while catching the real bug classes PR 4/PR 5 had to find by hand.
Suppress a deliberate exception with ``# lint: ok[rule-name]`` on the
flagged line.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple


@dataclass(frozen=True)
class Finding:
    file: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.file}:{self.line}:{self.col}: [{self.rule}] {self.message}"


def _chain(node: ast.AST) -> Optional[str]:
    """Dotted name for a Name/Attribute chain ('self.kv.buffers'), else
    None for anything with a non-trivial base."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _walk_skipping_defs(node: ast.AST):
    """Yield descendant nodes without descending into nested function or
    class definitions (their bodies run in another scope/time)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


def _functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class Rule:
    name = ""
    summary = ""

    def check(self, tree: ast.Module, filename: str) -> List[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# scatter-drop
# ---------------------------------------------------------------------------

class ScatterDropRule(Rule):
    name = "scatter-drop"
    summary = ('slot/row/block-table-indexed .at[...] writes must pass '
               'mode="drop"')

    #: index identifiers that mark a scatter as slot-pool / block-table /
    #: parked-position addressing — the indices that are out of range BY
    #: DESIGN (padding rows aim at num_slots, parked positions at
    #: PARK_POS) and rely on drop semantics to write nothing
    _PAT = re.compile(r"slot|row|table|block|park|trow|wslot|wblk|woff",
                      re.IGNORECASE)
    _WRITE_METHODS = frozenset({"set", "add", "multiply", "mul", "divide",
                                "max", "min", "apply"})

    def check(self, tree, filename):
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._WRITE_METHODS):
                continue
            sub = node.func.value
            if not (isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "at"):
                continue
            names: Set[str] = set()
            for n in ast.walk(sub.slice):
                if isinstance(n, ast.Name):
                    names.add(n.id)
                elif isinstance(n, ast.Attribute):
                    names.add(n.attr)
            hits = sorted(n for n in names if self._PAT.search(n))
            if not hits:
                continue
            mode = next((kw.value for kw in node.keywords
                         if kw.arg == "mode"), None)
            if isinstance(mode, ast.Constant) and mode.value == "drop":
                continue
            out.append(Finding(
                filename, node.lineno, node.col_offset, self.name,
                f".at[...].{node.func.attr} indexed by "
                f"{', '.join(hits)} must pass mode=\"drop\": slot/"
                "block-table indices carry out-of-range sentinels by "
                "design (padding rows, PARK_POS) and XLA's default "
                "out-of-bounds clamp would silently corrupt a real row"))
        return out


# ---------------------------------------------------------------------------
# state-thread
# ---------------------------------------------------------------------------

class StateThreadRule(Rule):
    name = "state-thread"
    summary = ('carried-state leaf .at[...] writes (conv/ssm/cross_k/'
               'cross_v) must pass mode="drop"')

    #: names that mark the write TARGET as a carried-state leaf
    #: (DESIGN.md §13): SSM/hybrid recurrent state and enc-dec cross
    #: K/V. Complements scatter-drop, which keys on the *index* name —
    #: a state scatter through an innocuously named index (``idx``)
    #: still addresses per-request rows whose padding sentinel is out
    #: of range by design, so the target name is the invariant here.
    _STATE = re.compile(r"\bconv\b|\bssm\b|cross_k|cross_v", re.IGNORECASE)
    _WRITE_METHODS = ScatterDropRule._WRITE_METHODS

    @staticmethod
    def _target_names(expr) -> Set[str]:
        """Identifiers mentioned in the expression being indexed (the X
        of ``X.at[...]``): variable names, attribute names, and string
        keys of dict-style cache access (``cache["conv"]``)."""
        names: Set[str] = set()
        for n in ast.walk(expr):
            if isinstance(n, ast.Name):
                names.add(n.id)
            elif isinstance(n, ast.Attribute):
                names.add(n.attr)
            elif isinstance(n, ast.Constant) and isinstance(n.value, str):
                names.add(n.value)
        return names

    def check(self, tree, filename):
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._WRITE_METHODS):
                continue
            sub = node.func.value
            if not (isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "at"):
                continue
            # the expression being scattered into, minus the ".at"
            hits = sorted(n for n in self._target_names(sub.value.value)
                          if self._STATE.search(n))
            if not hits:
                continue
            # a fully-constant index is a compile-time-checked address,
            # not a per-request scatter — out of scope
            if all(isinstance(n, ast.Constant)
                   for n in ast.walk(sub.slice)
                   if isinstance(n, (ast.Name, ast.Constant))):
                continue
            mode = next((kw.value for kw in node.keywords
                         if kw.arg == "mode"), None)
            if isinstance(mode, ast.Constant) and mode.value == "drop":
                continue
            out.append(Finding(
                filename, node.lineno, node.col_offset, self.name,
                f".at[...].{node.func.attr} into carried-state leaf "
                f"({', '.join(hits)}) must pass mode=\"drop\": state "
                "rows are per-request and their padding/parked indices "
                "are out of range by design — the default out-of-bounds "
                "clamp would overwrite a live request's scan state"))
        return out


# ---------------------------------------------------------------------------
# donated-use
# ---------------------------------------------------------------------------

class DonatedUseRule(Rule):
    name = "donated-use"
    summary = ("a buffer passed through a donate_argnums jit must not be "
               "read again before rebinding")

    @staticmethod
    def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
        """The donate_argnums of a ``jax.jit(...)`` call, or None when
        the call is not a donating jit."""
        fc = _chain(call.func)
        if fc not in ("jax.jit", "jit"):
            return None
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                elts = [e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
                return tuple(elts)
        return None

    def _collect_donating(self, scope: ast.AST) -> Dict[str, Tuple[int, ...]]:
        """Map of callable chain -> donated positions for assignments
        like ``self._decode = jax.jit(fn, donate_argnums=(1, 2))``
        anywhere under ``scope`` (a module or a class body)."""
        out: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(scope):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            pos = self._donated_positions(node.value)
            if pos is None:
                continue
            for tgt in node.targets:
                c = _chain(tgt)
                if c is not None:
                    out[c] = pos
        return out

    def check(self, tree, filename):
        out: List[Finding] = []
        module_map = self._collect_donating(tree)
        class_maps: Dict[int, Dict[str, Tuple[int, ...]]] = {}
        owner_class: Dict[int, Optional[ast.ClassDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                class_maps[id(node)] = self._collect_donating(node)
                for fn in node.body:
                    if isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                        owner_class[id(fn)] = node
        for fn in _functions(tree):
            cls = owner_class.get(id(fn))
            donating = dict(module_map)
            if cls is not None:
                donating.update(class_maps[id(cls)])
            out.extend(self._check_function(fn, donating, filename))
        return out

    def _check_function(self, fn, donating, filename) -> List[Finding]:
        # events ordered by (line, phase): loads first (call args are
        # loads on the kill line and must not flag), then kills, then
        # stores/revives (the canonical `buf = self._step(buf)` rebinds
        # on the same statement)
        LOAD, KILL, STORE = 0, 1, 2
        events: List[Tuple[int, int, str, ast.AST]] = []

        for node in _walk_skipping_defs(fn):
            if isinstance(node, ast.Call):
                pos = None
                fc = _chain(node.func)
                if fc is not None and fc in donating:
                    pos = donating[fc]
                elif isinstance(node.func, ast.Call):
                    pos = DonatedUseRule._donated_positions(node.func)
                if pos:
                    end = node.end_lineno or node.lineno
                    for p in pos:
                        if p < len(node.args):
                            c = _chain(node.args[p])
                            if c is not None:
                                events.append((end, KILL, c, node))
                # a mutating method call on a prefix of a donated chain
                # (self.kv.swap_buffers(...) after donating
                # self.kv.buffers) reinstalls the buffer: revive
                if isinstance(node.func, ast.Attribute):
                    base = _chain(node.func.value)
                    if base is not None:
                        events.append((node.end_lineno or node.lineno,
                                       STORE, base + ".*", node))
            elif isinstance(node, (ast.Name, ast.Attribute)) \
                    and isinstance(getattr(node, "ctx", None), ast.Load):
                c = _chain(node)
                if c is not None:
                    events.append((node.lineno, LOAD, c, node))
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.For,
                                   ast.AnnAssign, ast.withitem)):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                elif isinstance(node, ast.For):
                    targets = [node.target]
                elif isinstance(node, ast.withitem):
                    targets = [node.optional_vars] if node.optional_vars \
                        else []
                for t in targets:
                    line = getattr(node, "end_lineno", None) \
                        or getattr(t, "end_lineno", None) or t.lineno
                    for leaf in ast.walk(t):
                        c = _chain(leaf)
                        if c is not None:
                            events.append((line, STORE, c, node))

        events.sort(key=lambda e: (e[0], e[1]))
        dead: Dict[str, Tuple[int, ast.AST]] = {}
        out: List[Finding] = []
        for line, phase, chain, node in events:
            if phase == LOAD:
                hit = dead.get(chain)
                if hit is not None and line > hit[0]:
                    out.append(Finding(
                        filename, line, node.col_offset, self.name,
                        f"`{chain}` was donated to a jit at line "
                        f"{hit[0]} and read again before rebinding: "
                        "donated buffers are deleted by XLA aliasing — "
                        "use the jit's returned value (or rebind first)"))
                    del dead[chain]
            elif phase == KILL:
                dead[chain] = (line, node)
            else:  # STORE / revive
                if chain.endswith(".*"):
                    prefix = chain[:-2] + "."
                    for k in [k for k in dead if k.startswith(prefix)]:
                        del dead[k]
                else:
                    dead.pop(chain, None)
        return out


# ---------------------------------------------------------------------------
# request-leak
# ---------------------------------------------------------------------------

_ISSUE_OPS = frozenset({
    "isend", "irecv", "icollective", "iallreduce", "ireduce", "ibcast",
    "ibarrier", "iallgather", "ireduce_scatter",
})
_COMPLETE_OPS = frozenset({"wait", "test", "synchronize"})
_COMPLETE_FNS = frozenset({"waitall", "testall"})


class RequestLeakRule(Rule):
    name = "request-leak"
    summary = ("a Request from i*-ops must reach wait/test/waitall on "
               "every path")

    # hooks the span-leak rule overrides — the AST walk is identical,
    # only the issue/completion vocabulary and the wording differ
    _issue_attrs = _ISSUE_OPS
    _ctor: Optional[str] = "Request"
    _complete_attrs = _COMPLETE_OPS
    _complete_fns = _COMPLETE_FNS
    _noun = "Request"

    def _is_issue(self, call: ast.Call) -> bool:
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in self._issue_attrs:
            return True
        if self._ctor is None:
            return False
        c = _chain(call.func)
        return c is not None and c.split(".")[-1] == self._ctor

    def _msg_discard(self) -> str:
        return ("Request discarded at the call site: the operation "
                "is never completed — bind it and wait()/waitall() "
                "(or testall in a progress loop)")

    def _msg_leak(self, name: str) -> str:
        return (f"Request bound to `{name}` is never completed: no "
                "wait()/test()/waitall() reaches it in this "
                "function and it does not escape")

    def _msg_exception(self, name: str) -> str:
        return (f"Requests bound to `{name}` are issued inside a try "
                "body and only completed there: an exception mid-issue "
                "abandons every request already in flight — move the "
                "waitall/wait into the finally block")

    def check(self, tree, filename):
        out: List[Finding] = []
        for fn in _functions(tree):
            out.extend(self._check_function(fn, filename))
        return out

    def _check_function(self, fn, filename) -> List[Finding]:
        out: List[Finding] = []
        issues: Dict[str, List[ast.Call]] = {}   # binding -> issue calls
        escaped: Set[str] = set()
        completed: Dict[str, List[ast.AST]] = {}  # binding -> completions
        aliases: Dict[str, str] = {}              # loop var -> iterated list
        synchronized = False

        def bind_of(call: ast.Call, parents: Dict[int, ast.AST]
                    ) -> Optional[str]:
            """The name an issue call's result lands in; records escapes
            and discards along the way (None = handled elsewhere)."""
            p = parents.get(id(call))
            if isinstance(p, ast.Expr):
                out.append(Finding(
                    filename, call.lineno, call.col_offset, self.name,
                    self._msg_discard()))
                return None
            if isinstance(p, ast.Assign) and len(p.targets) == 1 \
                    and isinstance(p.targets[0], ast.Name):
                return p.targets[0].id
            if isinstance(p, ast.Call) and isinstance(p.func, ast.Attribute) \
                    and p.func.attr in ("append", "add", "insert") \
                    and isinstance(p.func.value, ast.Name):
                return p.func.value.id     # reqs.append(comm.isend(...))
            # returned / stored on self / passed to a helper: assume the
            # receiver owns completion
            return "<escaped>"

        parents: Dict[int, ast.AST] = {}
        for node in _walk_skipping_defs(fn):
            for child in ast.iter_child_nodes(node):
                parents.setdefault(id(child), node)
        for child in ast.iter_child_nodes(fn):
            parents.setdefault(id(child), fn)

        for node in _walk_skipping_defs(fn):
            if isinstance(node, ast.Call) and self._is_issue(node):
                b = bind_of(node, parents)
                if b and b != "<escaped>":
                    issues.setdefault(b, []).append(node)
            elif isinstance(node, ast.For) \
                    and isinstance(node.target, ast.Name) \
                    and isinstance(node.iter, ast.Name):
                aliases[node.target.id] = node.iter.id
            elif isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in self._complete_attrs:
                    if node.func.attr == "synchronize":
                        synchronized = True
                    base = node.func.value
                    if isinstance(base, ast.Name):
                        name = aliases.get(base.id, base.id)
                        completed.setdefault(name, []).append(node)
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in self._complete_fns:
                    for arg in node.args:
                        for n in ast.walk(arg):
                            if isinstance(n, ast.Name):
                                completed.setdefault(
                                    aliases.get(n.id, n.id), []
                                ).append(node)
            elif isinstance(node, (ast.Return, ast.Yield)) \
                    and node.value is not None:
                for n in ast.walk(node.value):
                    if isinstance(n, ast.Name):
                        escaped.add(n.id)
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                        for n in ast.walk(node.value):
                            if isinstance(n, ast.Name):
                                escaped.add(n.id)

        # a binding passed as an argument to any other call escapes
        for node in _walk_skipping_defs(fn):
            if isinstance(node, ast.Call):
                if self._is_issue(node):
                    continue
                fc = _chain(node.func)
                is_completion = (
                    (isinstance(node.func, ast.Attribute)
                     and node.func.attr in self._complete_attrs)
                    or (fc is not None
                        and fc.split(".")[-1] in self._complete_fns))
                if is_completion:
                    continue
                for arg in node.args:
                    if isinstance(arg, ast.Name) and arg.id in issues:
                        escaped.add(arg.id)

        for name, calls in issues.items():
            if synchronized or name in escaped or name in completed:
                self._check_exception_path(
                    fn, name, calls, completed.get(name, []), filename, out)
                continue
            for call in calls:
                out.append(Finding(
                    filename, call.lineno, call.col_offset, self.name,
                    self._msg_leak(name)))
        return out

    @staticmethod
    def _span(stmts: Sequence[ast.AST]) -> Tuple[int, int]:
        return (stmts[0].lineno,
                stmts[-1].end_lineno or stmts[-1].lineno)

    def _check_exception_path(self, fn, name, calls, completions,
                              filename, out) -> None:
        """Issues inside a try body whose only completions are also in
        the try body, with a finally that never completes them, leak on
        the exception path — the transport bug class."""
        if not completions:
            return
        for node in _walk_skipping_defs(fn):
            if not (isinstance(node, ast.Try) and node.finalbody):
                continue
            lo, hi = self._span(node.body)
            flo, fhi = self._span(node.finalbody)
            inside = [c for c in calls if lo <= c.lineno <= hi]
            if not inside:
                continue
            safe = [c for c in completions
                    if not (lo <= c.lineno <= hi)]
            if safe:
                continue
            out.append(Finding(
                filename, inside[0].lineno, inside[0].col_offset,
                self.name, self._msg_exception(name)))


# ---------------------------------------------------------------------------
# span-leak
# ---------------------------------------------------------------------------

_SPAN_ISSUE_OPS = frozenset({"span", "begin_span"})
_SPAN_COMPLETE_OPS = frozenset({"end", "end_span"})


class SpanLeakRule(RequestLeakRule):
    """Same AST shape as request-leak, retargeted at the manual span
    API (DESIGN.md §15): a handle from ``obs_trace.span(...)`` bound to
    a local name must reach ``end()`` on every path. Context-manager use
    (``with obs_trace.span(...):``) and handles that escape (returned,
    stored on ``self``, passed on) are exception-safe or owned elsewhere
    and never flagged — exactly the request-leak escape semantics. A
    span entered and never ended leaves its profiler annotation open and
    its ``rank=`` on the thread, mis-attributing everything after it."""

    name = "span-leak"
    summary = ("a manually-bound span must reach end() on every "
               "path (or be opened as a context manager)")

    _issue_attrs = _SPAN_ISSUE_OPS
    _ctor = None
    _complete_attrs = _SPAN_COMPLETE_OPS
    _complete_fns = frozenset()
    _noun = "Span"

    def _msg_discard(self) -> str:
        return ("Span discarded at the call site: it opens on the "
                "thread's span stack and is never ended — use "
                "`with obs_trace.span(...):` or bind the handle and "
                "end() it")

    def _msg_leak(self, name: str) -> str:
        return (f"Span bound to `{name}` is never ended: no end() "
                "reaches it in this function and it does not escape — "
                "the thread's span nesting leaks")

    def _msg_exception(self, name: str) -> str:
        return (f"Spans bound to `{name}` are opened inside a try body "
                "and only ended there: an exception leaves them on the "
                "thread's span stack — move the end() into the finally "
                "block (or use `with obs_trace.span(...):`)")


# ---------------------------------------------------------------------------
# stream-order
# ---------------------------------------------------------------------------

_BLOCKING_OPS = frozenset({
    "allreduce", "reduce", "bcast", "barrier", "allgather",
    "reduce_scatter", "alltoall", "send_recv",
})
_COMM_OPS = _BLOCKING_OPS | _ISSUE_OPS | frozenset({
    "split", "dup", "stream", "group", "run", "thread_comm",
    "process_comm", "set_attr", "get_attr",
})


class StreamOrderRule(Rule):
    name = "stream-order"
    summary = ("no blocking collective inside a stream region; no comm op "
               "after finish()/free() without start()")

    def check(self, tree, filename):
        out: List[Finding] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.With):
                self._check_stream_region(node, filename, out)
        for fn in _functions(tree):
            self._check_use_after_finish(fn, filename, out)
        return out

    @staticmethod
    def _is_stream_with(node: ast.With) -> bool:
        for item in node.items:
            e = item.context_expr
            if isinstance(e, ast.Call) and isinstance(e.func, ast.Attribute) \
                    and e.func.attr == "stream":
                return True
        return False

    def _check_stream_region(self, node: ast.With, filename, out) -> None:
        if not self._is_stream_with(node):
            return
        for stmt in node.body:
            for n in _walk_skipping_defs(stmt):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr in _BLOCKING_OPS:
                    out.append(Finding(
                        filename, n.lineno, n.col_offset, self.name,
                        f"blocking `{n.func.attr}` inside a CommStream "
                        "region: the stream exists to overlap — use the "
                        f"nonblocking `i{n.func.attr}` and wait() after "
                        "the region (a blocking call here also bypasses "
                        "the stream's ordering token)"))
            if isinstance(stmt, ast.Call) \
                    and isinstance(stmt.func, ast.Attribute) \
                    and stmt.func.attr in _BLOCKING_OPS:
                out.append(Finding(
                    filename, stmt.lineno, stmt.col_offset, self.name,
                    f"blocking `{stmt.func.attr}` inside a CommStream "
                    "region"))

    def _check_use_after_finish(self, fn, filename, out) -> None:
        closed: Dict[str, int] = {}    # comm chain -> line of finish/free
        sites: List[Tuple[int, str, str, ast.Call]] = []
        for node in _walk_skipping_defs(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                base = _chain(node.func.value)
                if base is None:
                    continue
                sites.append((node.lineno, base, node.func.attr, node))
        sites.sort(key=lambda s: s[0])
        for line, base, op, node in sites:
            if op in ("finish", "free"):
                closed.setdefault(base, line)
            elif op == "start":
                closed.pop(base, None)
            elif op in _COMM_OPS and base in closed:
                out.append(Finding(
                    filename, line, node.col_offset, self.name,
                    f"`{base}.{op}` after `{base}.finish()`/`free()` at "
                    f"line {closed[base]}: the activation window is "
                    "closed and every derived object is dead — call "
                    "start() to open a new window first"))


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

class HostSyncRule(Rule):
    name = "host-sync"
    summary = ("no host-synchronizing call inside a jit'd micro-step "
               "body")

    _SYNC_ATTRS = frozenset({"item", "tolist"})
    _SYNC_CHAINS = frozenset({
        "jax.device_get", "jax.block_until_ready", "np.asarray",
        "np.array", "numpy.asarray", "numpy.array",
    })

    @staticmethod
    def _jit_region_names(tree) -> Set[str]:
        """Names of function defs passed (by name or self-attribute) as
        the first argument of a jax.jit call anywhere in the module."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _chain(node.func) in ("jax.jit", "jit")
                    and node.args):
                continue
            a0 = node.args[0]
            if isinstance(a0, ast.Name):
                names.add(a0.id)
            elif isinstance(a0, ast.Attribute):
                names.add(a0.attr)
        return names

    @classmethod
    def _is_jit_region(cls, fn, jit_names: Set[str],
                       parent_fn: Optional[ast.AST]) -> bool:
        if fn.name in jit_names:
            return True
        for dec in fn.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            if _chain(d) in ("jax.jit", "jit"):
                return True
        # the engine's factory convention: `def _x_impl*(...)` returning
        # an inner `fn` that the caller jits
        if fn.name == "fn" and parent_fn is not None \
                and parent_fn.name.startswith("_") \
                and "impl" in parent_fn.name:
            return True
        return False

    def check(self, tree, filename):
        out: List[Finding] = []
        jit_names = self._jit_region_names(tree)
        parent_fn: Dict[int, ast.AST] = {}
        for fn in _functions(tree):
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node is not fn:
                    parent_fn.setdefault(id(node), fn)
        for fn in _functions(tree):
            if not self._is_jit_region(fn, jit_names,
                                       parent_fn.get(id(fn))):
                continue
            params = {a.arg for a in fn.args.args
                      + fn.args.posonlyargs + fn.args.kwonlyargs}
            for node in _walk_skipping_defs(fn):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._sync_call(node, params)
                if msg:
                    out.append(Finding(
                        filename, node.lineno, node.col_offset,
                        self.name,
                        f"{msg} inside jit region `{fn.name}`: forces a "
                        "device->host sync in the hot loop (and fails "
                        "under trace) — keep the value on device, sync "
                        "once per micro-step outside the jit"))
        return out

    def _sync_call(self, node: ast.Call, params: Set[str]) -> Optional[str]:
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in self._SYNC_ATTRS:
            return f"`.{node.func.attr}()`"
        fc = _chain(node.func)
        if fc in self._SYNC_CHAINS:
            if fc.endswith(("asarray", "array")):
                # np shape math on static host values is legitimate at
                # trace time; only flag converting a traced parameter
                if not (node.args and isinstance(node.args[0], ast.Name)
                        and node.args[0].id in params):
                    return None
            return f"`{fc}`"
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int") and node.args \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in params:
            return f"`{node.func.id}()` of a traced argument"
        return None


ALL_RULES: Tuple[Rule, ...] = (
    ScatterDropRule(),
    StateThreadRule(),
    DonatedUseRule(),
    RequestLeakRule(),
    SpanLeakRule(),
    StreamOrderRule(),
    HostSyncRule(),
)

RULES_BY_NAME: Dict[str, Rule] = {r.name: r for r in ALL_RULES}
