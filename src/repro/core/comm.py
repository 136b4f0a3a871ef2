"""Unified communicator API: one ``Comm`` interface over the N×M rank space.

This is the communication layer's single entry point (DESIGN.md §2). The
root communicator is a :class:`ThreadComm` built from mesh axes — the
paper's MPIX threadcomm fusing the process domain (slow, inter-pod axes)
with the thread domain (fast, intra-pod axes) into one process-major rank
space. Every *derived* communicator shares the same method surface:

    root = threadcomm_init(mesh, process_axes, thread_axes)
    with root.start():
        tcomm = root.thread_comm()        # fast-domain sub-comm family
        pcomm = root.process_comm()       # slow-domain sub-comm family
        sub   = root.split(color, key)    # MPI_Comm_split over unified ranks
        dup   = root.dup()                # same group, fresh context
        y = sub.allreduce(x)              # collectives are METHODS
        req = pcomm.iallreduce(x)         # nonblocking -> Request
        ... overlap compute ...
        y = req.wait()

Sub-communicators follow MPIX stream semantics (arXiv:2208.13707): a
``CommStream`` binds a comm to a named execution stream; requests issued on
a stream are serialized against each other via ``lax.optimization_barrier``
tokens, while independent streams may overlap. ``split`` returns an
axis-aligned :class:`AxisComm` (lowering to native psum/ppermute over mesh
axis names — the fast path) whenever the color classes coincide with a mesh
sub-grid, and a generic :class:`GroupComm` (merged ring schedules over the
full unified rank space) otherwise.

Lifetime rules extend the paper's §2 activation-window semantics: derived
comms, groups, attributes AND requests die at ``finish`` — using any of
them afterwards raises :class:`ThreadCommError`.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

# runtime threadcomm sanitizer (REPRO_SANITIZE=1, DESIGN.md §11): every
# hook below is a single global read + None check when disabled
from repro.analysis.sanitizer import active as _san_active
# span tracer + stall detector (REPRO_TRACE=1, DESIGN.md §15) — the
# same one-global-read-plus-None-check discipline when disabled
from repro.obs.trace import active as _tr_active
from repro.core import collectives as coll
from repro.core import p2p as p2p_mod
from repro.core import protocol
from repro.core.compat import shard_map


class ThreadCommError(RuntimeError):
    """Misuse of the communicator lifecycle / activation-window rules."""


CommError = ThreadCommError  # preferred alias for new code


# ---------------------------------------------------------------------------
# Requests (nonblocking operations)
# ---------------------------------------------------------------------------

class Request:
    """Handle for a nonblocking operation.

    Carries the operation's (traced or concrete) result plus an ordering
    token. ``wait()`` returns the result; ``test()`` polls completion
    without blocking. Like every threadcomm-derived object, a request is
    only valid inside the activation window that issued it (paper §2): a
    ``wait`` after ``finish`` raises :class:`ThreadCommError`.

    ``model_overhead_s`` carries the protocol model's request-object cost
    (0 for the eager-fast path that skips request allocation — §3.2).
    """

    __slots__ = ("comm", "op", "_value", "_epoch", "_done", "stream",
                 "model_overhead_s")

    def __init__(self, comm: "Comm", op: str, value,
                 stream: Optional["CommStream"] = None,
                 model_overhead_s: float = 0.0):
        self.comm = comm
        self.op = op
        self._value = value
        self._epoch = comm._root._epoch
        self._done = False
        self.stream = stream
        self.model_overhead_s = model_overhead_s
        san = _san_active()
        if san is not None:
            san.on_request(self)

    def _check_window(self):
        self.comm._root._check_not_freed()
        if self._epoch != self.comm._root._epoch:
            raise ThreadCommError(
                f"request({self.op}) outlived its activation window "
                "(derived objects die at finish)")

    def wait(self):
        """Complete the operation and return its result. A runtime failure
        of the operation (device error, poisoned buffer) surfaces HERE —
        wait() is the completion point — not at a later use site."""
        self._check_window()
        self._done = True
        san = _san_active()
        if san is not None:
            san.on_request_complete(self)
        value = self._value
        leaves = jax.tree_util.tree_leaves(value)
        if not any(isinstance(l, jax.core.Tracer) for l in leaves):
            tr = _tr_active()
            if tr is None:
                jax.block_until_ready(value)   # host-level completion
            else:
                # the completion point is where accidental serialization
                # bites: time the block, and let the stall detector
                # charge it when this thread had runnable work
                t0 = time.perf_counter()
                jax.block_until_ready(value)   # host-level completion
                tr.on_wait(self.op, t0, time.perf_counter())
        return value

    def test(self) -> Tuple[bool, Optional[object]]:
        """(done, result_or_None) without blocking. Under a trace every op
        is scheduled into the dataflow graph, so it reports done."""
        self._check_window()
        if self._done:
            return True, self._value
        leaves = jax.tree_util.tree_leaves(self._value)
        ready = all(bool(getattr(l, "is_ready", lambda: True)())
                    for l in leaves)
        if ready:
            self._done = True
            san = _san_active()
            if san is not None:
                san.on_request_complete(self)
            return True, self._value
        return False, None


def waitall(requests: Sequence[Request]) -> List[object]:
    """MPI_Waitall: complete every request, preserving order."""
    return [r.wait() for r in requests]


def testall(requests: Sequence[Request]) -> bool:
    """MPI_Testall: True iff every request has completed."""
    return all(r.test()[0] for r in requests)


class CommStream:
    """A named execution stream bound to a comm (the MPIX stream analogue).

    Requests issued while the stream is entered are serialized against each
    other by threading an ``optimization_barrier`` token from each issue to
    the next — explicit program-order for communication, independent of any
    other stream. Use one stream per overlap domain, e.g.::

        with comm.stream("grad") as s:
            req = pcomm.iallreduce(shard)   # ordered on "grad"
        ... backward / optimizer math overlaps here ...
        shard = req.wait()
    """

    def __init__(self, comm: "Comm", name: str):
        self.comm = comm
        self.name = name
        self._token = None
        self._requests: List[Request] = []
        self._obs_span = None

    def __enter__(self) -> "CommStream":
        self.comm._root._check_active()
        san = _san_active()
        if san is not None:       # program order flows into the stream
            san.on_stream_enter(self)
        tr = _tr_active()
        if tr is not None:        # stream-region span, closed in __exit__
            self._obs_span = tr.span(f"stream:{self.name}", cat="comm")
        self.comm._root._stream_stack.append(self)
        return self

    def __exit__(self, *exc):
        stack = self.comm._root._stream_stack
        if stack and stack[-1] is self:
            stack.pop()
        sp = self._obs_span
        if sp is not None:
            self._obs_span = None
            sp.end()
        return False

    # ---- token plumbing (called by Comm.icollective) ----
    def _gate(self, x):
        if self._token is None:
            return x
        gated, _ = lax.optimization_barrier((x, self._token))
        return gated

    def _record(self, req: Request):
        leaves = jax.tree_util.tree_leaves(req._value)
        if leaves:
            self._token = leaves[0]
        self._requests.append(req)

    def synchronize(self) -> List[object]:
        """Complete every request issued on this stream (in order)."""
        out = waitall(self._requests)
        self._requests = []
        return out

    def ordered(self, value):
        """Thread an arbitrary pytree through this stream's program order:
        ``value`` is gated on the stream's last recorded token and its first
        leaf becomes the new tail. This is how non-collective work (e.g. the
        serving engine's prefill inserts and decode micro-steps, DESIGN.md
        §8) joins a stream's serialization context without going through
        ``icollective`` — same MPIX-stream semantics, ordering *within* the
        stream, none against other streams."""
        self.comm._root._check_active()
        leaves = jax.tree_util.tree_leaves(value)
        if not leaves:
            return value
        if self._token is not None:
            leaves = list(lax.optimization_barrier(
                tuple(leaves) + (self._token,)))[:-1]
            value = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(value), leaves)
        # record a COPY of a 1-element slice: the caller may donate `value`
        # into its next step (the serving engine does), which must not
        # delete the stream tail — and the tail must not pin a full buffer
        # (the prefill stream's first leaf is a whole KV page). The tail
        # is always float32, so each gate above compiles once per value
        # structure, whatever kind of value the stream ordered last.
        self._token = jnp.copy(leaves[0].ravel()[:1].astype(jnp.float32))
        return value


# ---------------------------------------------------------------------------
# Derived-object handle (rank subsets) — kept from the MPIX group API
# ---------------------------------------------------------------------------

@dataclass
class Group:
    """A subset of unified ranks derived from an active comm. Valid only
    within the activation window that created it (paper §2)."""
    comm: "Comm"
    ranks: Tuple[int, ...]
    _epoch: int = 0

    def _check(self):
        self.comm._root._check_active()
        if self._epoch != self.comm._root._epoch:
            raise ThreadCommError(
                "group outlived its threadcomm activation window "
                "(derived objects die at MPIX_Threadcomm_finish)")

    @property
    def size(self) -> int:
        self._check()
        return len(self.ranks)

    def translate(self, rank: int) -> int:
        self._check()
        return self.ranks[rank]


# ---------------------------------------------------------------------------
# The unified Comm interface
# ---------------------------------------------------------------------------

class Comm:
    """Common surface of every communicator (root and derived).

    Collectives/p2p are methods; ``i``-prefixed variants return
    :class:`Request`. Subclasses provide ``_axes()`` (mesh axis names the
    op spans, or None for the generic ppermute path), ``size``,
    ``families()`` (host-side unified-rank lists), and the blocking
    collective implementations.
    """

    _root: "ThreadComm"

    # -- lifecycle ---------------------------------------------------------
    def _check(self):
        self._root._check_active()
        if self._birth_epoch != self._root._epoch:
            raise ThreadCommError(
                "communicator outlived its parent's activation window "
                "(derived comms die at finish)")

    @property
    def _birth_epoch(self) -> int:
        return self._epoch_at_birth

    # -- identity ----------------------------------------------------------
    @property
    def size(self) -> int:  # pragma: no cover - overridden
        raise NotImplementedError

    def families(self) -> List[List[int]]:
        """Host-side: the concurrent sub-comm instances this object stands
        for, each as a list of unified ranks ordered by local rank. The
        root comm is a single family spanning every rank."""
        raise NotImplementedError

    def translate(self, local_rank: int, family: int = 0) -> int:
        """Local rank -> unified (root) rank, MPI_Group_translate_ranks."""
        self._check()
        return self.families()[family][local_rank]

    def local_rank(self):
        """Traced local rank of the calling device (inside shard_map)."""
        raise NotImplementedError

    # -- derivation --------------------------------------------------------
    def dup(self) -> "Comm":
        """Same group(s), fresh communication context (MPI_Comm_dup). The
        dup is still a derived object: it dies at the parent's finish."""
        self._check()
        return self._clone()

    def _clone(self) -> "Comm":  # pragma: no cover - overridden
        raise NotImplementedError

    def split(self, color: Sequence[int], key: Optional[Sequence[int]] = None
              ) -> "Comm":
        """MPI_Comm_split over each family: local ranks with equal
        ``color[local_rank]`` form a sub-comm, ordered by
        ``(key[local_rank], local_rank)``. color < 0 == MPI_UNDEFINED (the
        rank joins no sub-comm and passes collectives through untouched).

        Returns an :class:`AxisComm` when the classes tile an axis-aligned
        mesh sub-grid in natural order (the fast path), else a
        :class:`GroupComm`.
        """
        self._check()
        color = list(color)
        if len(color) != self.size:
            raise ThreadCommError(
                f"split color has {len(color)} entries for a size-"
                f"{self.size} comm")
        if key is not None and len(key) != self.size:
            raise ThreadCommError("split key length must equal comm size")
        groups: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
        for fam_idx, fam in enumerate(self.families()):
            for lr, ur in enumerate(fam):
                c = color[lr]
                if c < 0:
                    continue
                k = key[lr] if key is not None else lr
                groups.setdefault((fam_idx, c), []).append((k, lr, ur))
        ordered = [tuple(ur for _, _, ur in sorted(v))
                   for _, v in sorted(groups.items())]
        natural = key is None or all(
            list(g) == sorted(g) for g in ordered)
        if natural:
            axes = self._root._axis_aligned(ordered)
            if axes is not None:
                return AxisComm(self._root, axes)
        return GroupComm(self._root, ordered)

    def stream(self, name: str) -> CommStream:
        """A named execution stream bound to this comm (MPIX stream)."""
        self._check()
        return CommStream(self, name)

    def _current_stream(self) -> Optional[CommStream]:
        stack = self._root._stream_stack
        return stack[-1] if stack else None

    # -- blocking collectives (subclass responsibility) --------------------
    def allreduce(self, x, schedule: str = "psum", wire_dtype=None):
        raise NotImplementedError

    def reduce(self, x, root: int = 0, schedule: str = "binomial"):
        raise NotImplementedError

    def bcast(self, x, root: int = 0):
        raise NotImplementedError

    def barrier(self, token, mode: str = "msg"):
        raise NotImplementedError

    def allgather(self, x, tiled: bool = True):
        raise NotImplementedError

    def reduce_scatter(self, x):
        raise NotImplementedError

    def alltoall(self, x):
        raise NotImplementedError

    def send_recv(self, x, pairs, *, force_protocol: Optional[str] = None):
        raise NotImplementedError

    # -- nonblocking layer -------------------------------------------------
    def icollective(self, op: str, x, *args, **kw) -> Request:
        """Issue collective ``op`` nonblocking: returns a :class:`Request`
        carrying the result plus a stream-ordering token."""
        self._check()
        stream = self._current_stream()
        if stream is not None:
            x = stream._gate(x)
        value = getattr(self, op)(x, *args, **kw)
        req = Request(self, op, value, stream=stream)
        if stream is not None:
            stream._record(req)
        return req

    def iallreduce(self, x, schedule: str = "psum", wire_dtype=None) -> Request:
        return self.icollective("allreduce", x, schedule, wire_dtype)

    def ireduce(self, x, root: int = 0, schedule: str = "binomial") -> Request:
        return self.icollective("reduce", x, root, schedule)

    def ibcast(self, x, root: int = 0) -> Request:
        return self.icollective("bcast", x, root)

    def ibarrier(self, token, mode: str = "msg") -> Request:
        return self.icollective("barrier", token, mode)

    def iallgather(self, x, tiled: bool = True) -> Request:
        return self.icollective("allgather", x, tiled)

    def ireduce_scatter(self, x) -> Request:
        return self.icollective("reduce_scatter", x)

    def _is_interthread(self) -> bool:
        """True when every message on this comm stays inside one process
        (the fast shared domain) — drives protocol selection and the
        request-skip fast path, which are interthread-only (§3.2)."""
        return all(len({self._root.process_of(r) for r in fam}) <= 1
                   for fam in self.families())

    def isend(self, x, pairs, *, force_protocol: Optional[str] = None
              ) -> Request:
        """Nonblocking rank-addressed message round. Under the static SPMD
        schedule send and receive are one fused permute (DESIGN.md §7), so
        the request's value is the RECEIVED buffer. The request carries the
        protocol model's request-object overhead — zero on the eager-fast
        path, which skips request allocation (paper §3.2; interthread
        comms only — slow-domain messages always pay the request)."""
        self._check()
        stream = self._current_stream()
        if stream is not None:
            x = stream._gate(x)
        nbytes = x.size * x.dtype.itemsize
        interthread = self._is_interthread()
        proto = force_protocol or protocol.select_protocol(
            int(nbytes), interthread=interthread)
        value = self.send_recv(x, pairs, force_protocol=proto)
        req = Request(self, f"sendrecv[{proto}]", value, stream=stream,
                      model_overhead_s=protocol.request_overhead(
                          int(nbytes), proto))
        if stream is not None:
            stream._record(req)
        return req

    irecv = isend  # SPMD: the matching receive of the same fused permute


# ---------------------------------------------------------------------------
# AxisComm: comms whose families tile mesh axes (fast, native lowering)
# ---------------------------------------------------------------------------

class AxisComm(Comm):
    """A family of sub-communicators spanning ``axes`` of the root mesh —
    one instance per coordinate of the complement axes, all operating
    concurrently (exactly MPI_Comm_split with color = complement coords).
    Collectives lower to the native / schedule-explicit implementations in
    :mod:`repro.core.collectives` over the axis names."""

    def __init__(self, root: "ThreadComm", axes: Tuple[str, ...]):
        self._root = root
        self.axes = tuple(axes)
        self._epoch_at_birth = root._epoch
        sizes = root._axis_sizes
        self._size = math.prod(sizes[a] for a in self.axes) if self.axes else 1

    @property
    def size(self) -> int:
        return self._size

    def _clone(self) -> "AxisComm":
        return AxisComm(self._root, self.axes)

    def families(self) -> List[List[int]]:
        root = self._root
        comp = [a for a in root.unified_axes if a not in self.axes]
        fams: Dict[Tuple[int, ...], List[Tuple[int, int]]] = {}
        for ur in range(root.size):
            coords = root.coords_of(ur)
            fkey = tuple(coords[a] for a in comp)
            lr = 0
            for a in self.axes:
                lr = lr * root._axis_sizes[a] + coords[a]
            fams.setdefault(fkey, []).append((lr, ur))
        return [[ur for _, ur in sorted(v)] for _, v in sorted(fams.items())]

    def local_rank(self):
        r = np.int32(0)
        for ax in self.axes:
            r = r * self._root._axis_sizes[ax] + lax.axis_index(ax)
        return r

    # -- collectives -------------------------------------------------------
    def allreduce(self, x, schedule: str = "psum", wire_dtype=None):
        self._check()
        if not self.axes:
            return x
        return coll.allreduce(x, self.axes, schedule=schedule,
                              wire_dtype=wire_dtype)

    def reduce(self, x, root: int = 0, schedule: str = "binomial"):
        self._check()
        if not self.axes:
            return x
        return coll.reduce(x, self.axes, root=root, schedule=schedule)

    def bcast(self, x, root: int = 0):
        self._check()
        if not self.axes:
            return x
        return coll.bcast(x, self.axes, root=root)

    def barrier(self, token, mode: str = "msg"):
        self._check()
        if not self.axes:
            return token
        return coll.barrier(token, self.axes, mode=mode)

    def allgather(self, x, tiled: bool = True):
        self._check()
        if not self.axes:
            return x
        return coll.allgather(x, self.axes, tiled=tiled)

    def reduce_scatter(self, x):
        self._check()
        if not self.axes:
            return x
        return coll.reduce_scatter(x, self.axes)

    def alltoall(self, x):
        self._check()
        if not self.axes:
            return x
        return coll.alltoall(x, self.axes)

    def send_recv(self, x, pairs, *, force_protocol: Optional[str] = None):
        """One message round addressed by LOCAL ranks; applies to every
        family concurrently. Protocol selection (eager padding vs 1-copy)
        follows core.p2p, using this comm's domain (interthread vs
        interprocess) for the thresholds."""
        self._check()
        proto = force_protocol or protocol.select_protocol(
            int(x.size * x.dtype.itemsize),
            interthread=self._is_interthread())
        recv, _ = p2p_mod.send_recv(x, self.axes, list(pairs),
                                    force_protocol=proto)
        return recv


# ---------------------------------------------------------------------------
# GroupComm: arbitrary rank classes (merged ring schedules)
# ---------------------------------------------------------------------------

class GroupComm(Comm):
    """Sub-comms over arbitrary unified-rank classes. Collectives run as
    ring schedules over the FULL unified axes, with each class's ring
    merged into shared ``ppermute`` rounds (classes are disjoint, so their
    pairs compose). Ranks in no class pass through untouched.

    Generic and correct for any partition; prefer an axis-aligned
    :class:`AxisComm` (what ``split`` returns when it can) for bandwidth-
    optimal native lowering.
    """

    def __init__(self, root: "ThreadComm", groups: Sequence[Sequence[int]]):
        self._root = root
        self._epoch_at_birth = root._epoch
        self.groups: Tuple[Tuple[int, ...], ...] = tuple(
            tuple(g) for g in groups)
        seen = set()
        for g in self.groups:
            for r in g:
                if r in seen:
                    raise ThreadCommError(
                        f"rank {r} appears in two split classes")
                seen.add(r)
        sizes = {len(g) for g in self.groups}
        self._uniform = len(sizes) == 1
        self._max_k = max(sizes) if sizes else 1
        # host tables over the full unified space
        S = root.size
        pos = np.zeros(S, np.int32)
        ksz = np.ones(S, np.int32)
        member = np.zeros(S, bool)
        for g in self.groups:
            for i, r in enumerate(g):
                pos[r], ksz[r], member[r] = i, len(g), True
        self._pos_np, self._ksz_np, self._member_np = pos, ksz, member

    @property
    def size(self) -> int:
        if not self._uniform:
            raise ThreadCommError(
                "size is per-class on a non-uniform split; use .groups")
        return self._max_k

    def _clone(self) -> "GroupComm":
        return GroupComm(self._root, self.groups)

    def families(self) -> List[List[int]]:
        return [list(g) for g in self.groups]

    def local_rank(self):
        ur = self._root.device_rank()
        return jnp.take(jnp.asarray(self._pos_np), ur)

    # -- merged ring rounds ------------------------------------------------
    def _ring_pairs(self, t: int) -> List[Tuple[int, int]]:
        """Pairs of round ``t`` (0-based): every class still propagating
        (k - 1 rounds for a class of size k) rotates by one."""
        pairs = []
        for g in self.groups:
            k = len(g)
            if t < k - 1:
                pairs.extend((g[i], g[(i + 1) % k]) for i in range(k))
        return pairs

    def _ring_accumulate(self, x, combine: Callable):
        axes = self._root.unified_axes
        carry, acc = x, x
        for t in range(self._max_k - 1):
            pairs = self._ring_pairs(t)
            if not pairs:
                break
            carry = lax.ppermute(carry, axes, pairs)
            acc = combine(acc, carry)
        return acc

    # -- collectives -------------------------------------------------------
    def allreduce(self, x, schedule: str = "ring", wire_dtype=None):
        self._check()
        if wire_dtype is not None:
            wire = jnp.dtype(wire_dtype)
            axes = self._root.unified_axes
            carry, acc = x, x
            for t in range(self._max_k - 1):
                carry = lax.ppermute(carry.astype(wire), axes,
                                     self._ring_pairs(t)).astype(x.dtype)
                acc = acc + carry
            return acc
        return self._ring_accumulate(x, lambda a, c: a + c)

    def reduce(self, x, root: int = 0, schedule: str = "ring"):
        """Sum-reduce; every class rank holds the class total (the ring
        accumulate is symmetric, so non-root 'garbage' equals the sum)."""
        self._check()
        return self._ring_accumulate(x, lambda a, c: a + c)

    def barrier(self, token, mode: str = "msg"):
        self._check()
        token = jnp.asarray(token, jnp.float32)
        return self._ring_accumulate(token, jnp.maximum)

    def bcast(self, x, root: int = 0):
        """Broadcast each class's ``root``-th member (by local rank) to the
        class: the value propagates one hop per round; non-members keep x."""
        self._check()
        axes = self._root.unified_axes
        pos = jnp.take(jnp.asarray(self._pos_np), self._root.device_rank())
        ksz = jnp.take(jnp.asarray(self._ksz_np), self._root.device_rank())
        dist = jnp.mod(pos - root, ksz)
        v = x
        for t in range(1, self._max_k):
            pairs = self._ring_pairs(t - 1)
            if not pairs:
                break
            recv = lax.ppermute(v, axes, pairs)
            v = jnp.where(dist == t, recv, v)
        return v

    def allgather(self, x, tiled: bool = True):
        """Gather over each class; requires uniform class size (SPMD output
        shapes must agree across every device). ``tiled=True`` (the
        interface-wide default, matching AxisComm) concatenates along the
        leading dim; ``tiled=False`` stacks a new (k, ...) dim."""
        self._check()
        if not self._uniform:
            raise ThreadCommError("allgather needs uniform split classes")
        k = self._max_k
        axes = self._root.unified_axes
        pos = jnp.take(jnp.asarray(self._pos_np), self._root.device_rank())
        out = jnp.zeros((k,) + x.shape, x.dtype)
        out = lax.dynamic_update_slice_in_dim(out, x[None], pos, axis=0)
        carry = x
        for t in range(1, k):
            carry = lax.ppermute(carry, axes, self._ring_pairs(0))
            out = lax.dynamic_update_slice_in_dim(
                out, carry[None], jnp.mod(pos - t, k), axis=0)
        if tiled:
            out = out.reshape((k * x.shape[0],) + x.shape[1:])
        return out

    def reduce_scatter(self, x):
        self._check()
        if not self._uniform:
            raise ThreadCommError("reduce_scatter needs uniform classes")
        k = self._max_k
        total = self.allreduce(x)
        flat = total.reshape(-1)
        if flat.size % k:
            raise ThreadCommError(
                f"reduce_scatter payload ({flat.size}) must be divisible "
                f"by the class size {k}")
        shard = flat.size // k
        pos = jnp.take(jnp.asarray(self._pos_np), self._root.device_rank())
        return lax.dynamic_slice_in_dim(flat, pos * shard, shard)

    def alltoall(self, x):
        raise NotImplementedError(
            "alltoall on arbitrary split classes; use an axis-aligned split")

    def send_recv(self, x, pairs, *, force_protocol: Optional[str] = None):
        """Message round addressed by LOCAL class ranks (same pairs applied
        in every class)."""
        self._check()
        unified = []
        for src, dst in pairs:
            for g in self.groups:
                unified.append((g[src % len(g)], g[dst % len(g)]))
        proto = force_protocol or protocol.select_protocol(
            int(x.size * x.dtype.itemsize),
            interthread=self._is_interthread())
        recv, _ = p2p_mod.send_recv(x, self._root.unified_axes, unified,
                                    force_protocol=proto)
        return recv


# ---------------------------------------------------------------------------
# Root communicator: the threadcomm
# ---------------------------------------------------------------------------

class _ActivationWindow:
    """Returned by ``ThreadComm.start()``. Activation is EAGER (start() is
    MPIX_Threadcomm_start); use as a context manager for the canonical
    start/finish pair, or call ``finish()`` explicitly for service-style
    long-lived activations (e.g. a trainer that stays resident)."""

    def __init__(self, comm: "ThreadComm"):
        self._comm = comm

    def __enter__(self) -> "ThreadComm":
        return self._comm

    def __exit__(self, *exc):
        self.finish()
        return False

    def finish(self):
        self._comm.finish()


class ThreadComm(Comm):
    """Root communicator over ``process_axes`` × ``thread_axes``: the
    paper's unified N×M rank space with process-major ordering, carrying
    the MPIX lifecycle (init → start → ... → finish → free) that bounds the
    lifetime of every derived object."""

    def __init__(self, mesh: jax.sharding.Mesh,
                 process_axes: Sequence[str],
                 thread_axes: Sequence[str]):
        names = mesh.axis_names
        for ax in (*process_axes, *thread_axes):
            if ax not in names:
                raise ThreadCommError(f"axis {ax!r} not in mesh {names}")
        if set(process_axes) & set(thread_axes):
            raise ThreadCommError("process and thread axes must be disjoint")
        self.mesh = mesh
        self.process_axes = tuple(process_axes)
        self.thread_axes = tuple(thread_axes)
        self._root = self
        self._active = False
        self._freed = False
        self._epoch = 0
        self._attrs: Dict = {}
        self._stream_stack: List[CommStream] = []
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.num_processes = math.prod(
            sizes[a] for a in self.process_axes) if self.process_axes else 1
        self.threads_per_process = math.prod(
            sizes[a] for a in self.thread_axes) if self.thread_axes else 1
        self._size = self.num_processes * self.threads_per_process
        self._axis_sizes = sizes

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def _check_not_freed(self):
        if self._freed:
            raise ThreadCommError("threadcomm already freed")

    def _check_active(self):
        self._check_not_freed()
        if not self._active:
            raise ThreadCommError(
                "threadcomm is inactive: call start() (MPIX_Threadcomm_start)"
                " before communicating")

    def _check(self):  # the root's own window never goes stale
        self._check_active()

    def start(self) -> _ActivationWindow:
        """Activate the communicator (MPIX_Threadcomm_start). Eager: the
        window opens at the call. ``with tc.start():`` closes it at exit
        (MPIX_Threadcomm_finish); bare ``tc.start()`` + ``tc.finish()`` is
        the service-mode spelling for long-lived activations."""
        self._check_not_freed()
        if self._active:
            raise ThreadCommError("threadcomm already active (nested start)")
        self._active = True
        return _ActivationWindow(self)

    def finish(self):
        """Close the activation window: derived comms, groups, attributes
        and outstanding requests all become invalid (paper §2)."""
        self._check_not_freed()
        if not self._active:
            raise ThreadCommError("finish without a matching start")
        san = _san_active()
        if san is not None:       # pending requests die with the window
            san.on_finish(self)
        self._active = False
        self._attrs.clear()        # attribute lifetime = activation window
        self._stream_stack.clear()
        self._epoch += 1

    def free(self):
        self._check_not_freed()
        if self._active:
            raise ThreadCommError("cannot free an active threadcomm "
                                  "(call finish first)")
        self._freed = True

    # ------------------------------------------------------------------
    # rank arithmetic (host side)
    # ------------------------------------------------------------------
    @property
    def size(self) -> int:
        return self._size

    @property
    def unified_axes(self) -> Tuple[str, ...]:
        return self.process_axes + self.thread_axes

    def rank_of(self, coords: dict) -> int:
        """Unified rank for mesh coordinates — process-major (paper §2)."""
        r = 0
        for ax in self.unified_axes:
            r = r * self._axis_sizes[ax] + coords[ax]
        return r

    def coords_of(self, rank: int) -> dict:
        out = {}
        for ax in reversed(self.unified_axes):
            out[ax] = rank % self._axis_sizes[ax]
            rank //= self._axis_sizes[ax]
        return out

    def process_of(self, rank: int) -> int:
        return rank // self.threads_per_process

    def thread_of(self, rank: int) -> int:
        return rank % self.threads_per_process

    def families(self) -> List[List[int]]:
        return [list(range(self.size))]

    def local_rank(self):
        return self.device_rank()

    def group(self, ranks: Sequence[int]) -> Group:
        self._check_active()
        return Group(self, tuple(ranks), _epoch=self._epoch)

    # attributes (paper: lifetime bounded by the activation window)
    def set_attr(self, key, value):
        self._check_active()
        self._attrs[key] = value

    def get_attr(self, key):
        self._check_active()
        return self._attrs.get(key)

    # ------------------------------------------------------------------
    # derivation
    # ------------------------------------------------------------------
    def _clone(self) -> "AxisComm":
        return AxisComm(self, self.unified_axes)

    def process_comm(self) -> AxisComm:
        """Slow-domain family: one sub-comm of the N processes per thread
        index (ranks differing only in process coords)."""
        self._check_active()
        return AxisComm(self, self.process_axes)

    def thread_comm(self) -> AxisComm:
        """Fast-domain family: one sub-comm of the M threads per process
        (the intra-pod / shared-memory analogue domain)."""
        self._check_active()
        return AxisComm(self, self.thread_axes)

    def _axis_aligned(self, groups: Sequence[Sequence[int]]
                      ) -> Optional[Tuple[str, ...]]:
        """If ``groups`` exactly tile some axes-subset sub-grid in row-major
        local order, return those axes (split fast path)."""
        from itertools import combinations
        all_ranks = sorted(r for g in groups for r in g)
        if all_ranks != list(range(self.size)):
            return None
        want = {tuple(g) for g in groups}
        axes_list = list(self.unified_axes)
        for k in range(len(axes_list), -1, -1):
            for axes in combinations(axes_list, k):
                fams = AxisComm(self, axes).families()
                if {tuple(f) for f in fams} == want:
                    return axes
        return None

    # ------------------------------------------------------------------
    # device-side rank (call inside shard_map)
    # ------------------------------------------------------------------
    def device_rank(self):
        r = np.int32(0)
        for ax in self.unified_axes:
            r = r * self._axis_sizes[ax] + lax.axis_index(ax)
        return r

    # ------------------------------------------------------------------
    # SPMD launcher
    # ------------------------------------------------------------------
    def run(self, fn: Callable, *args, in_specs=None, out_specs=None):
        """shard_map a function over the full unified mesh. Default specs
        shard the leading dim over all unified axes (SPMD over ranks)."""
        self._check_active()
        in_specs = in_specs if in_specs is not None else P(self.unified_axes)
        out_specs = out_specs if out_specs is not None else P(self.unified_axes)
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs)(*args)

    # ------------------------------------------------------------------
    # collectives over the unified rank space
    # ------------------------------------------------------------------
    def allreduce(self, x, schedule: str = "psum", wire_dtype=None):
        self._check_active()
        if schedule == "hierarchical":
            return self._hierarchical_allreduce(x, wire_dtype=wire_dtype)
        if schedule == "hierarchical_tree":
            return self._hierarchical_tree_allreduce(x)
        return coll.allreduce(x, self.unified_axes, schedule=schedule,
                              wire_dtype=wire_dtype)

    def _hierarchical_allreduce(self, x, wire_dtype=None):
        """The paper's two-level schedule as a sub-comm composition:
        thread_comm.reduce_scatter → process_comm.allreduce (1/M bytes on
        the slow domain) → thread_comm.allgather."""
        tcomm, pcomm = self.thread_comm(), self.process_comm()
        if tcomm.size == 1:
            return pcomm.allreduce(x, wire_dtype=wire_dtype)
        shape, dtype = x.shape, x.dtype
        flat = x.reshape(-1)
        pad = (-flat.size) % tcomm.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        shard = tcomm.reduce_scatter(flat)
        if pcomm.size > 1:
            shard = pcomm.allreduce(shard, wire_dtype=wire_dtype)
        full = tcomm.allgather(shard, tiled=True)
        if pad:
            full = full[:-pad]
        return full.reshape(shape).astype(dtype)

    def _hierarchical_tree_allreduce(self, x):
        """Latency-oriented composition over derived comms (small payloads):
        thread_comm.reduce → process_comm.allreduce → thread_comm.bcast."""
        tcomm, pcomm = self.thread_comm(), self.process_comm()
        y = tcomm.reduce(x, root=0, schedule="binomial") if tcomm.size > 1 else x
        if pcomm.size > 1:
            y = pcomm.allreduce(y)
        return tcomm.bcast(y, root=0) if tcomm.size > 1 else y

    def barrier(self, token, mode: str = "msg"):
        self._check_active()
        return coll.barrier(token, self.unified_axes, mode=mode)

    def reduce(self, x, root: int = 0, schedule: str = "binomial"):
        self._check_active()
        return coll.reduce(x, self.unified_axes, root=root, schedule=schedule)

    def bcast(self, x, root: int = 0):
        self._check_active()
        return coll.bcast(x, self.unified_axes, root=root)

    def allgather(self, x, tiled: bool = True):
        self._check_active()
        return coll.allgather(x, self.unified_axes, tiled=tiled)

    def reduce_scatter(self, x):
        self._check_active()
        return coll.reduce_scatter(x, self.unified_axes)

    def alltoall(self, x):
        self._check_active()
        return coll.alltoall(x, self.unified_axes)

    def send_recv(self, x, pairs, *, force_protocol: Optional[str] = None):
        self._check_active()
        if force_protocol is None:
            return coll.sendrecv(x, self.unified_axes, pairs)
        recv, _ = p2p_mod.send_recv(x, self.unified_axes, list(pairs),
                                    force_protocol=force_protocol)
        return recv


def threadcomm_init(mesh, process_axes: Sequence[str] = (),
                    thread_axes: Optional[Sequence[str]] = None,
                    num_threads: Optional[int] = None) -> ThreadComm:
    """MPIX_Threadcomm_init analogue. ``num_threads``, when given, must match
    the thread-axes product (the paper's creation-parameter check)."""
    if thread_axes is None:
        thread_axes = tuple(a for a in mesh.axis_names
                            if a not in tuple(process_axes))
    tc = ThreadComm(mesh, process_axes, thread_axes)
    if num_threads is not None and num_threads != tc.threads_per_process:
        raise ThreadCommError(
            f"num_threads={num_threads} does not match the parallel region "
            f"width {tc.threads_per_process}")
    return tc
