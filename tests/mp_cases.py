"""Multi-device test cases, executed in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N (see tests/helpers.py).

Each case asserts internally and prints CASE-OK on success.
"""

import os
import sys

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from functools import partial

from repro.core.compat import make_mesh, shard_map


def _flat_mesh(n=8):
    return make_mesh((n,), ("ranks",))


def _hier_mesh(n=2, m=4):
    return make_mesh((n, m), ("proc", "thread"))


# ---------------------------------------------------------------------------

def case_collectives_flat():
    from repro.core import collectives as coll
    n = 8
    mesh = _flat_mesh(n)
    x = jnp.arange(n, dtype=jnp.float32) + 1.0          # rank r holds r+1

    def run(fn, inp=x, out_specs=P("ranks")):
        return shard_map(fn, mesh=mesh, in_specs=P("ranks"),
                             out_specs=out_specs)(inp)

    # barrier (msg): output token must be max over all ranks
    tok = run(lambda v: coll.barrier(v[0], "ranks", mode="msg")[None])
    assert np.allclose(np.asarray(tok), n), tok
    tok = run(lambda v: coll.barrier(v[0], "ranks", mode="atomic")[None])
    assert np.allclose(np.asarray(tok), n), tok

    # reduce (binomial) to root 0 and root 3
    total = float(n * (n + 1) / 2)
    for root in (0, 3):
        r = run(lambda v: coll.reduce(v, "ranks", root=root,
                                      schedule="binomial"))
        assert np.asarray(r)[root] == total, (root, r)

    # bcast from root 5: everyone ends with 6.0
    b = run(lambda v: coll.bcast(v, "ranks", root=5))
    assert np.allclose(np.asarray(b), 6.0), b

    # allreduce schedules agree with psum
    for schedule in ("psum", "recursive_doubling", "ring", "reduce_bcast"):
        big = jnp.arange(n * 24, dtype=jnp.float32).reshape(n, 24)
        out = shard_map(
            lambda v: coll.allreduce(v, "ranks", schedule=schedule),
            mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"))(big)
        want = np.tile(np.asarray(big).reshape(n, 24).sum(0), (n, 1))
        got = np.asarray(out).reshape(n, 24)
        assert np.allclose(got, want, rtol=1e-5), (schedule, got[:, :4])

    # allgather / reduce_scatter round trip == psum
    vec = jnp.arange(n * 4, dtype=jnp.float32)
    rs_ag = shard_map(
        lambda v: coll.allgather(coll.reduce_scatter(v, "ranks"), "ranks"),
        mesh=mesh, in_specs=P(None), out_specs=P(None), check_vma=False)(vec)
    assert np.allclose(np.asarray(rs_ag), np.asarray(vec) * n)

    # alltoall: transpose of rank/chunk grid
    mat = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)
    a2a = shard_map(
        lambda v: coll.alltoall(v.reshape(n, 1), "ranks").reshape(1, n),
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"))(mat)
    assert np.allclose(np.asarray(a2a), np.asarray(mat).T)

    # sendrecv: explicit pairs (ring shift by 2)
    pairs = [(i, (i + 2) % n) for i in range(n)]
    sr = shard_map(lambda v: coll.sendrecv(v, "ranks", pairs),
                       mesh=mesh, in_specs=P("ranks"),
                       out_specs=P("ranks"))(x)
    want = np.roll(np.asarray(x), 2)
    assert np.allclose(np.asarray(sr), want), sr
    print("CASE-OK")


def case_threadcomm_unified():
    from repro.core import threadcomm_init, ThreadCommError
    from repro.core import collectives as coll
    n_proc, m_thread = 2, 4
    mesh = _hier_mesh(n_proc, m_thread)
    tc = threadcomm_init(mesh, process_axes=("proc",),
                         thread_axes=("thread",), num_threads=m_thread)
    assert tc.size == n_proc * m_thread
    assert tc.num_processes == n_proc and tc.threads_per_process == m_thread

    # process-major rank ordering (paper §2): rank = proc*M + thread
    assert tc.rank_of({"proc": 1, "thread": 2}) == 6
    assert tc.coords_of(6) == {"thread": 2, "proc": 1}
    assert tc.process_of(5) == 1

    # inactive comm refuses to communicate
    try:
        tc.allreduce(jnp.ones(4))
        raise SystemExit("inactive comm should have raised")
    except ThreadCommError:
        pass

    with tc.start():
        # Listing 1/2 reproduction: every device reports rank/size
        ranks = tc.run(lambda x: x + tc.device_rank().astype(jnp.float32),
                       jnp.zeros(tc.size))
        assert np.allclose(np.sort(np.asarray(ranks)), np.arange(tc.size))

        # unified flat allreduce == psum over all axes
        x = jnp.arange(tc.size, dtype=jnp.float32)
        out = tc.run(lambda v: tc.allreduce(v, schedule="recursive_doubling"),
                     x)
        assert np.allclose(np.asarray(out), np.asarray(x).sum())

        # hierarchical == flat (numerics), vector length coprime to M
        vec = jnp.arange(tc.size * 13, dtype=jnp.float32).reshape(tc.size, 13)
        h = tc.run(lambda v: tc.allreduce(v, schedule="hierarchical"), vec)
        f = tc.run(lambda v: tc.allreduce(v, schedule="psum"), vec)
        assert np.allclose(np.asarray(h), np.asarray(f), rtol=1e-5)

        g = tc.group(list(range(4)))
        assert g.size == 4 and g.translate(2) == 2
        tc.set_attr("petsc", 42)
        assert tc.get_attr("petsc") == 42

    # derived objects die at finish (paper lifetime rule)
    with tc.start():
        try:
            g.size
            raise SystemExit("stale group should have raised")
        except ThreadCommError:
            pass
        assert tc.get_attr("petsc") is None

    # nested start forbidden; free-while-active forbidden
    with tc.start():
        try:
            tc.start().__enter__()
            raise SystemExit("nested start should have raised")
        except ThreadCommError:
            pass
    tc.free()
    try:
        tc.allreduce(jnp.ones(3))
        raise SystemExit("freed comm should have raised")
    except ThreadCommError:
        pass
    print("CASE-OK")


def case_p2p_protocols():
    from repro.core import p2p
    n = 8
    mesh = _flat_mesh(n)
    pairs = [(i, (i + 1) % n) for i in range(n)]

    for elems, want_proto in ((64, "eager_fast"), (1024, "eager_fast"),
                              (1 << 16, "one_copy")):
        x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)

        def f(v):
            recv, _ = p2p.send_recv(v, "ranks", pairs)
            return recv

        out = shard_map(f, mesh=mesh, in_specs=P("ranks"),
                            out_specs=P("ranks"))(x)
        want = np.roll(np.asarray(x), 1, axis=0)
        assert np.allclose(np.asarray(out), want), elems
        from repro.core import protocol
        assert protocol.select_protocol(elems * 4) == want_proto, elems

    # halo exchange
    x = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)

    def g(v):
        fl, fr = p2p.halo_exchange_1d(v, "ranks", n)
        return jnp.concatenate([fl, fr], 0)

    out = shard_map(g, mesh=mesh, in_specs=P("ranks"),
                        out_specs=P("ranks"))(x)
    out = np.asarray(out).reshape(n, 2, 4)
    xs = np.asarray(x).reshape(n, 1, 4)
    for i in range(n):
        assert np.allclose(out[i, 0], xs[(i - 1) % n, -1])  # from left
        assert np.allclose(out[i, 1], xs[(i + 1) % n, 0])   # from right
    print("CASE-OK")


def case_hierarchical_collective_bytes():
    """Hierarchical allreduce must emit smaller inter-process (slow-axis)
    collectives than flat: check the lowered HLO collective structure."""
    from repro.core import collectives as coll
    mesh = _hier_mesh(2, 4)
    nbytes = 4 * 1024
    x = jnp.zeros(8 * nbytes // 4, jnp.float32)

    def flat(v):
        return lax.psum(v, ("proc", "thread"))

    def hier(v):
        return coll.hierarchical_allreduce(v, process_axes=("proc",),
                                           thread_axes=("thread",))

    def hlo(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(None),
                                     out_specs=P(None), check_vma=False)
                       ).lower(x).compile().as_text()

    flat_txt, hier_txt = hlo(flat), hlo(hier)
    assert "all-reduce" in flat_txt
    assert "reduce-scatter" in hier_txt and "all-gather" in hier_txt
    print("CASE-OK")


def case_grad_sync_parity():
    """spmd / threadcomm / flat grad-sync modes must produce the same
    training trajectory (they differ only in collective schedule)."""
    from repro.config import MeshConfig, TrainConfig, ServeConfig
    from repro.configs import get_smoke_config
    from repro.data import SyntheticPipeline
    from repro.models.registry import build_model
    from repro.train.trainer import init_train_state, make_train_step
    from repro.dist.sharding import batch_pspec
    from jax.sharding import NamedSharding

    cfg = get_smoke_config("yi-9b")
    mesh_cfg = MeshConfig(shape=(2, 2, 2),
                          axis_names=("pod", "data", "model"),
                          process_axes=("pod",))
    mesh = make_mesh(mesh_cfg.shape, mesh_cfg.axis_names)
    pipe = SyntheticPipeline(cfg, batch=8, seq_len=16, seed=0)
    b_shard = NamedSharding(mesh, batch_pspec(mesh_cfg))

    from repro.train.explicit import init_explicit_state

    losses = {}
    for mode in ("spmd", "threadcomm", "flat"):
        tcfg = TrainConfig(param_dtype="float32", compute_dtype="float32",
                           loss_chunk=16, attn_chunk_threshold=64,
                           remat=False, grad_sync=mode, learning_rate=1e-2,
                           warmup_steps=1, total_steps=10)
        model = build_model(cfg, tcfg, ServeConfig(), tp=2)
        if mode == "spmd":
            state = init_train_state(model, jax.random.PRNGKey(0))
            step = jax.jit(make_train_step(model, mesh_cfg, tcfg))
        else:
            state = init_explicit_state(model, jax.random.PRNGKey(0), dp=4)
            step = make_train_step(model, mesh_cfg, tcfg, mesh=mesh)
        ls = []
        for i in range(3):
            batch = {k: jax.device_put(jnp.asarray(v), b_shard)
                     for k, v in pipe.get_batch(i).items()}
            state, metrics = step(state, batch)
            ls.append(float(metrics["loss"]))
        losses[mode] = ls
    for mode in ("threadcomm", "flat"):
        assert np.allclose(losses[mode], losses["spmd"],
                           rtol=1e-4, atol=1e-4), losses
    print("losses:", losses)
    print("CASE-OK")


def case_elastic_remesh():
    """Checkpoint written under one mesh restores onto a different mesh
    shape with identical values (elastic re-mesh)."""
    import tempfile
    from repro.config import MeshConfig, TrainConfig, ServeConfig
    from repro.configs import get_smoke_config
    from repro.models.registry import build_model
    from repro.train import checkpoint as ckpt
    from repro.train.trainer import init_train_state
    from repro.dist.sharding import param_pspecs, named_sharding

    cfg = get_smoke_config("qwen3-14b")
    tcfg = TrainConfig(param_dtype="float32", compute_dtype="float32",
                       remat=False)
    model = build_model(cfg, tcfg, ServeConfig(), tp=4)
    state = init_train_state(model, jax.random.PRNGKey(0))

    mesh_a_cfg = MeshConfig(shape=(2, 4), axis_names=("data", "model"))
    mesh_b_cfg = MeshConfig(shape=(4, 2), axis_names=("data", "model"))
    mesh_a = make_mesh(mesh_a_cfg.shape, mesh_a_cfg.axis_names)
    mesh_b = make_mesh(mesh_b_cfg.shape, mesh_b_cfg.axis_names)

    spec_a = param_pspecs(cfg, mesh_a_cfg, state.params)
    params_a = jax.device_put(state.params,
                              named_sharding(mesh_a, spec_a))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 5, params_a, extra={"mesh": list(mesh_a_cfg.shape)})
        spec_b = param_pspecs(cfg, mesh_b_cfg, state.params)
        restored, step, extra = ckpt.restore(
            d, state.params, shardings=named_sharding(mesh_b, spec_b))
        assert step == 5 and extra["mesh"] == [2, 4]
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b)), state.params, restored)
        # restored arrays live on the NEW mesh
        leaf = jax.tree_util.tree_leaves(restored)[0]
        assert leaf.sharding.mesh.shape == dict(data=4, model=2)
    print("CASE-OK")


def case_spmv_distributed():
    """Slab-decomposed 27pt stencil MatMult over 8 unified ranks == oracle,
    for several cube sizes (halo exchange via threadcomm p2p)."""
    from repro.apps.spmv import make_distributed_matmult, stencil_matmult_ref
    for n in (8, 16, 24):
        mesh = _flat_mesh(8)
        x = jax.random.normal(jax.random.PRNGKey(n), (n, n, n))
        mm = make_distributed_matmult("ranks", 8)
        y = jax.jit(shard_map(mm, mesh=mesh, in_specs=P("ranks"),
                                  out_specs=P("ranks")))(x)
        ref = stencil_matmult_ref(x)
        assert np.allclose(np.asarray(y), np.asarray(ref), atol=1e-4), n
    # hierarchical mesh too: (2 proc x 4 thread) unified ranks
    from repro.core import threadcomm_init
    mesh = _hier_mesh(2, 4)
    tc = threadcomm_init(mesh, process_axes=("proc",),
                         thread_axes=("thread",))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 16))
    with tc.start():
        mm = make_distributed_matmult(tc.unified_axes, tc.size)
        y = tc.run(mm, x)
    assert np.allclose(np.asarray(y), np.asarray(stencil_matmult_ref(x)),
                       atol=1e-4)
    print("CASE-OK")


def case_grad_compression_parity():
    """bf16 inter-pod gradient wire (threadcomm, §Perf cell A iter.2) must
    track the f32 trajectory within bf16 tolerance."""
    from repro.config import MeshConfig, TrainConfig, ServeConfig
    from repro.configs import get_smoke_config
    from repro.data import SyntheticPipeline
    from repro.models.registry import build_model
    from repro.train.trainer import make_train_step
    from repro.train.explicit import init_explicit_state
    from repro.dist.sharding import batch_pspec
    from jax.sharding import NamedSharding

    cfg = get_smoke_config("yi-9b")
    mesh_cfg = MeshConfig(shape=(2, 2, 2),
                          axis_names=("pod", "data", "model"),
                          process_axes=("pod",))
    mesh = make_mesh(mesh_cfg.shape, mesh_cfg.axis_names)
    pipe = SyntheticPipeline(cfg, batch=8, seq_len=16, seed=0)
    b_shard = NamedSharding(mesh, batch_pspec(mesh_cfg))
    losses = {}
    for wire in ("float32", "bfloat16"):
        tcfg = TrainConfig(param_dtype="float32", compute_dtype="float32",
                           loss_chunk=16, attn_chunk_threshold=64,
                           remat=False, grad_sync="threadcomm",
                           grad_comm_dtype=wire, learning_rate=1e-2,
                           warmup_steps=1, total_steps=10)
        model = build_model(cfg, tcfg, ServeConfig(), tp=2)
        state = init_explicit_state(model, jax.random.PRNGKey(0), dp=4)
        step = make_train_step(model, mesh_cfg, tcfg, mesh=mesh)
        ls = []
        for i in range(3):
            batch = {k: jax.device_put(jnp.asarray(v), b_shard)
                     for k, v in pipe.get_batch(i).items()}
            state, metrics = step(state, batch)
            ls.append(float(metrics["loss"]))
        losses[wire] = ls
    assert np.allclose(losses["bfloat16"], losses["float32"],
                       rtol=2e-2, atol=2e-2), losses
    print("losses:", losses)
    print("CASE-OK")


def case_comm_split_dup():
    """Unified Comm API: split/dup derivation and rank translation over a
    2-axis (process × thread) mesh."""
    from repro.core.comm import (AxisComm, GroupComm, ThreadCommError,
                                 threadcomm_init)
    n_proc, m_thread = 2, 4
    mesh = _hier_mesh(n_proc, m_thread)
    tc = threadcomm_init(mesh, process_axes=("proc",), thread_axes=("thread",))
    with tc.start():
        # canonical derivations
        tcm, pcm = tc.thread_comm(), tc.process_comm()
        assert tcm.size == m_thread and pcm.size == n_proc
        # thread_comm families: one per process, local rank == thread index
        assert tcm.families() == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert pcm.families() == [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert tcm.translate(2, family=1) == 6
        assert pcm.translate(1, family=3) == 7

        # split by process color == thread_comm (axis-aligned fast path)
        s = tc.split([r // m_thread for r in range(tc.size)])
        assert isinstance(s, AxisComm) and s.axes == ("thread",), s
        # split by thread color == process_comm
        s2 = tc.split([r % m_thread for r in range(tc.size)])
        assert isinstance(s2, AxisComm) and s2.axes == ("proc",)
        # color=constant == dup of the whole comm
        s3 = tc.split([0] * tc.size)
        assert isinstance(s3, AxisComm) and set(s3.axes) == {"proc", "thread"}
        # non-grid split (parity classes) takes the generic path
        g = tc.split([r % 2 for r in range(tc.size)])
        assert isinstance(g, GroupComm)
        assert g.groups == ((0, 2, 4, 6), (1, 3, 5, 7))
        assert g.translate(1, family=1) == 3
        # key reorders local ranks within a class
        gk = tc.split([0] * tc.size, key=list(range(tc.size))[::-1])
        assert gk.families()[0] == list(range(tc.size))[::-1]
        # MPI_UNDEFINED: negative color joins no class
        gu = tc.split([0, 0, 0, 0, -1, -1, -1, -1])
        assert gu.families() == [[0, 1, 2, 3]]
        # dup: same group, fresh context
        d = tc.dup()
        assert d.size == tc.size and d is not tc
        assert tcm.dup().families() == tcm.families()
        # bad color vector length
        try:
            tc.split([0])
            raise SystemExit("short color vector should have raised")
        except ThreadCommError:
            pass
    tc.free()
    print("CASE-OK")


def case_comm_subcomm_collectives():
    """Derived sub-comm collectives on a ≥2-axis mesh: axis-aligned split
    classes reduce independently; generic (non-grid) classes agree with a
    per-class oracle; hierarchical allreduce is the sub-comm composition
    and matches flat psum."""
    from repro.core.comm import AxisComm, GroupComm, threadcomm_init
    n_proc, m_thread = 2, 4
    mesh = _hier_mesh(n_proc, m_thread)
    tc = threadcomm_init(mesh, process_axes=("proc",), thread_axes=("thread",))
    x = jnp.arange(float(tc.size)) + 1.0          # rank r holds r+1
    with tc.start():
        # per-process sums via the split-derived thread comm
        sub = tc.split([r // m_thread for r in range(tc.size)])
        out = tc.run(lambda v: sub.allreduce(v), x)
        want = np.array([sum(range(1, 5))] * 4 + [sum(range(5, 9))] * 4, float)
        assert np.allclose(np.asarray(out), want), out

        # generic split: parity classes, ring path
        g = tc.split([r % 2 for r in range(tc.size)])
        out = tc.run(lambda v: g.allreduce(v), x)
        want = np.zeros(tc.size)
        for grp in g.groups:
            s = sum(r + 1.0 for r in grp)
            for r in grp:
                want[r] = s
        assert np.allclose(np.asarray(out), want), (out, want)
        # bcast from class-local root 0
        outb = tc.run(lambda v: g.bcast(v, root=0), x)
        wantb = np.zeros(tc.size)
        for grp in g.groups:
            for r in grp:
                wantb[r] = grp[0] + 1.0
        assert np.allclose(np.asarray(outb), wantb), (outb, wantb)
        # allgather (uniform classes): every rank sees its class's vector;
        # tiled (interface default) and stacked agree
        outg = tc.run(lambda v: g.allgather(v[0], tiled=False)[None].sum(1),
                      x[:, None])
        assert np.allclose(np.asarray(outg).ravel(), want), outg
        outt = tc.run(lambda v: g.allgather(v)[:1] * 0
                      + g.allgather(v).sum(), x[:, None])
        assert np.allclose(np.asarray(outt).ravel(), want), outt

        # hierarchical allreduce == flat psum, both compositions
        vec = jnp.arange(tc.size * 13, dtype=jnp.float32).reshape(tc.size, 13)
        flat = tc.run(lambda v: tc.allreduce(v, schedule="psum"), vec)
        for sched in ("hierarchical", "hierarchical_tree"):
            h = tc.run(lambda v, s=sched: tc.allreduce(v, schedule=s), vec)
            assert np.allclose(np.asarray(h), np.asarray(flat),
                               rtol=1e-5), sched
        # sub-comm p2p: ring shift within each process via thread_comm
        tcm = tc.thread_comm()
        pairs = [(i, (i + 1) % m_thread) for i in range(m_thread)]
        sr = tc.run(lambda v: tcm.send_recv(v, pairs), x)
        want = np.concatenate([np.roll(np.asarray(x)[:4], 1),
                               np.roll(np.asarray(x)[4:], 1)])
        assert np.allclose(np.asarray(sr), want), sr
    tc.free()
    print("CASE-OK")


def case_comm_requests():
    """Request-based nonblocking ops: iallreduce == blocking allreduce,
    wait/test protocol, stream-ordered issue, isend/irecv protocol cost."""
    from repro.core import protocol
    from repro.core.comm import threadcomm_init, waitall
    n_proc, m_thread = 2, 4
    mesh = _hier_mesh(n_proc, m_thread)
    tc = threadcomm_init(mesh, process_axes=("proc",), thread_axes=("thread",))
    x = jnp.arange(float(tc.size))
    with tc.start():
        blocking = tc.run(lambda v: tc.allreduce(v), x)

        def nonblocking(v):
            req = tc.iallreduce(v)
            done, _ = req.test()     # under trace the op is scheduled
            assert done
            return req.wait()
        got = tc.run(nonblocking, x)
        assert np.allclose(np.asarray(got), np.asarray(blocking))

        # stream-ordered pipeline: two dependent requests on one stream
        tcm, pcm = tc.thread_comm(), tc.process_comm()

        def pipeline(v):
            flat = v.reshape(-1)                 # (8,) per rank
            with tc.stream("grad") as s:
                r1 = tcm.ireduce_scatter(flat)   # (2,) shard, fast domain
                r2 = pcm.iallreduce(r1.wait())   # slow domain on 1/M bytes
                full = tcm.iallgather(r2.wait()).wait()
                assert len(s._requests) == 3
            return full.reshape(v.shape)
        payload = jnp.tile(x[:, None], (1, 8))
        out = tc.run(pipeline, payload)
        flat = tc.run(lambda v: tc.allreduce(v), payload)
        assert np.allclose(np.asarray(out), np.asarray(flat))

        # waitall preserves order
        def many(v):
            reqs = [tc.iallreduce(v), tc.iallreduce(2 * v)]
            a, b = waitall(reqs)
            return a + b
        out = tc.run(many, x)
        assert np.allclose(np.asarray(out), 3 * np.asarray(x).sum())

        # isend: small INTERTHREAD payloads ride the request-free eager
        # fast path; the root comm crosses processes, so its messages
        # always pay the request object (the fast path is §3.2's
        # interthread-only optimization)
        tpairs = [(i, (i + 1) % m_thread) for i in range(m_thread)]
        def ring_thread(v):
            req = tcm.isend(v, tpairs)
            assert req.model_overhead_s == 0.0       # eager_fast
            return req.wait()
        out = tc.run(ring_thread, x)
        want = np.concatenate([np.roll(np.asarray(x)[:4], 1),
                               np.roll(np.asarray(x)[4:], 1)])
        assert np.allclose(np.asarray(out), want)
        pairs = [(i, (i + 1) % tc.size) for i in range(tc.size)]
        def ring_root(v):
            req = tc.isend(v, pairs)
            assert req.model_overhead_s > 0.0        # cross-process
            return req.wait()
        out = tc.run(ring_root, x)
        assert np.allclose(np.asarray(out), np.roll(np.asarray(x), 1))
        big = jnp.zeros((tc.size, 1 << 12), jnp.float32)
        def ring_big(v):
            req = tcm.isend(v, tpairs)
            assert req.model_overhead_s > 0.0        # one_copy: real request
            return req.wait()
        tc.run(ring_big, big)
        assert protocol.request_overhead(64) == 0.0
        assert protocol.request_overhead(1 << 20) > 0.0
    tc.free()
    print("CASE-OK")


def case_comm_epoch_invalidation():
    """Activation-window semantics extend to derived comms and requests:
    anything issued inside a window dies at finish() (paper §2)."""
    from repro.core.comm import ThreadCommError, threadcomm_init
    mesh = _hier_mesh(2, 4)
    tc = threadcomm_init(mesh, process_axes=("proc",), thread_axes=("thread",))
    x = jnp.arange(8.0)

    captured = {}
    with tc.start():
        captured["sub"] = tc.thread_comm()
        captured["dup"] = tc.dup()
        captured["split"] = tc.split([r % 2 for r in range(8)])

        def issue(v):
            captured["req"] = tc.iallreduce(v)
            return captured["req"].wait()        # valid inside the window
        out = tc.run(issue, x)
        assert np.allclose(np.asarray(out), np.asarray(x).sum())
        req2 = captured["req"]
        assert req2.test()[0]                    # still inside the window

    # window closed: every derived object must refuse to operate
    with tc.start():
        for name in ("sub", "dup", "split"):
            try:
                captured[name].dup()
                raise SystemExit(f"stale {name} comm should have raised")
            except ThreadCommError:
                pass
        try:
            captured["req"].wait()
            raise SystemExit("stale request should have raised")
        except ThreadCommError:
            pass
        try:
            captured["req"].test()
            raise SystemExit("stale request test() should have raised")
        except ThreadCommError:
            pass
        # a fresh window issues fresh derived objects that DO work
        fresh = tc.thread_comm()
        out = tc.run(lambda v: fresh.allreduce(v), x)
        assert np.allclose(
            np.asarray(out),
            np.concatenate([np.full(4, np.asarray(x)[:4].sum()),
                            np.full(4, np.asarray(x)[4:].sum())]))
    tc.free()
    print("CASE-OK")


def case_dryrun_smoke():
    """Reduced-config dry-run cells lower+compile on the production meshes
    (the full configs run via launch/dryrun.py --all)."""
    import tempfile
    os.environ["REPRO_ARTIFACT_DIR"] = tempfile.mkdtemp()
    from repro.launch.dryrun import run_cell
    for arch, shape, mesh in (("gemma-2b", "train_4k", "single_pod"),
                              ("mamba2-370m", "decode_32k", "multi_pod"),
                              ("olmoe-1b-7b", "train_4k", "multi_pod")):
        res = run_cell(arch, shape, mesh, smoke=True, verbose=False)
        assert "analysis" in res, (arch, shape, mesh)
        assert res["analysis"]["terms"]["compute_s"] > 0
    print("CASE-OK")


def case_serve_replica_fanout():
    """Serving replica fan-out (DESIGN.md §8): data-parallel replicas are
    ``Comm.split`` families over the unified rank space; each replica
    serves its round-robin ``shard_trace`` slice, and replica-internal
    collectives (token-budget allreduce) stay confined to the family."""
    from repro.core import threadcomm_init
    from repro.serve import make_trace, shard_trace

    n, n_rep = 8, 2
    mesh = _flat_mesh(n)
    root = threadcomm_init(mesh, process_axes=(), thread_axes=("ranks",))
    root.start()

    trace = make_trace(12, prompt_len=8, max_new=(2, 6), seed=3)
    shards = [shard_trace(trace, i, n_rep) for i in range(n_rep)]
    # the fan-out partitions the traffic: disjoint, exhaustive, balanced
    assert sum(len(s) for s in shards) == len(trace)
    assert not {id(e) for e in shards[0]} & {id(e) for e in shards[1]}
    assert abs(len(shards[0]) - len(shards[1])) <= 1

    # replicas = contiguous half-blocks of the flat 8-rank axis: not an
    # axis-aligned sub-grid, so split takes the merged-ring GroupComm path
    color = [r * n_rep // n for r in range(n)]
    rep = root.split(color)
    assert len(rep.families()) == n_rep and rep.size == n // n_rep

    # replica-internal token-budget allreduce: every rank of replica i must
    # see replica i's total, with no leakage from the other replica
    toks = [float(sum(e.max_new for e in s)) for s in shards]
    per_rank = jnp.asarray([toks[color[r]] for r in range(n)],
                           dtype=jnp.float32)
    out = shard_map(lambda v: rep.allreduce(v), mesh=mesh,
                    in_specs=P("ranks"), out_specs=P("ranks"))(per_rank)
    expect = np.array([toks[color[r]] * (n // n_rep) for r in range(n)])
    assert np.allclose(np.asarray(out), expect), (out, expect)

    root.finish()
    root.free()
    print("CASE-OK")


def case_comm_waitall_mixed():
    """``waitall`` over MIXED send/recv requests on a split sub-comm —
    the fabric's KV-handoff pattern (DESIGN.md §10): a source rank
    streams payload pieces to its partner over a dedicated stream while
    an independent allreduce request rides alongside, and the single
    ``waitall`` completion point covers them all in issue order."""
    from repro.core.comm import threadcomm_init, testall, waitall

    n = 8
    mesh = _flat_mesh(n)
    tc = threadcomm_init(mesh, process_axes=(), thread_axes=("ranks",))
    with tc.start():
        # two split families of 4 ranks each (contiguous halves — the
        # merged-ring GroupComm path, like the fabric's engine comms)
        color = [r // 4 for r in range(n)]
        sub = tc.split(color)
        assert len(sub.families()) == 2 and sub.size == 4

        x = jnp.arange(float(n)) + 1.0
        # local-rank pairs: 0->1, 1->0 (the prefill->decode hop and the
        # decode rank's ack), applied in each family concurrently
        pairs = [(0, 1), (1, 0)]

        def handoff(v):
            with sub.stream("kv-migrate") as s:
                reqs = []
                # "blocks": three chunked isends of growing payloads —
                # forced one_copy, the rendezvous-class a KV block rides
                for piece in (v, 2 * v, 3 * v):
                    reqs.append(sub.isend(piece, pairs,
                                          force_protocol="one_copy"))
                # a recv handle for the same round (SPMD: the matching
                # receive of the fused permute) + an unrelated collective
                reqs.append(sub.irecv(4 * v, pairs))
                reqs.append(sub.iallreduce(v))
                assert len(s._requests) == 5
                assert testall(reqs)       # traced: all scheduled
                out = waitall(reqs)        # one completion point, in order
            # every one_copy message paid its request object (§3.2: the
            # request-free path is eager_fast only)
            assert all(r.model_overhead_s > 0.0 for r in reqs[:3])
            return sum(out[:4]) + out[4]
        got = tc.run(handoff, x)

        xs = np.asarray(x)
        want = np.zeros(n)
        for fam in sub.families():
            fam_sum = xs[list(fam)].sum()
            for src, dst in pairs:
                # pieces 1x,2x,3x,4x of the src rank land on dst
                want[fam[dst]] += 10 * xs[fam[src]]
            for r in fam:
                want[r] += fam_sum                    # the allreduce ride
        assert np.allclose(np.asarray(got), want), (got, want)

    # derived comm dies with the activation window (the fabric's close())
    survived = False
    try:
        tc.run(lambda v: sub.isend(v, pairs).wait(), x)
        survived = True
    except Exception:
        pass
    assert not survived, "stale sub-comm survived finish"
    tc.free()
    print("CASE-OK")


def case_fabric_rank_devices():
    """Fabric ranks on their own devices: rank i's params, KV pool and
    decode state live on local device i, under both placements (disagg
    migrates KV blocks device to device), and every greedy token matches
    a single engine on device 0."""
    from repro.config import ServeConfig, TrainConfig
    from repro.configs import get_smoke_config
    from repro.models.registry import build_model, make_synthetic_batch
    from repro.serve import (ContinuousEngine, ServeRequest, ServingFabric,
                             make_trace)

    n = len(jax.devices())
    cfg = get_smoke_config("gemma-2b")
    model = build_model(cfg, TrainConfig(param_dtype="float32",
                                         compute_dtype="float32",
                                         remat=False), ServeConfig(), tp=1)
    params = model.init(jax.random.PRNGKey(0))
    trace = make_trace(8, prompt_len=(16, 40), max_new=(3, 8),
                       arrival="all", seed=0)

    def requests():
        return [ServeRequest(
            rid=rid, batch={"tokens": np.asarray(make_synthetic_batch(
                cfg, 1, e.prompt_len, seed=1000 + rid,
                compute_dtype="float32")["tokens"])},
            max_new_tokens=e.max_new, temperature=0.0, seed=0)
            for rid, e in enumerate(trace)]

    def drain(target, reqs):
        for r in reqs:
            target.submit(r, 0.0)
        for _ in range(4000):
            if target.idle:
                break
            target.step(0.0)
        assert target.idle, "failed to drain"
        return [r.output[:r.generated].copy() for r in reqs]

    kw = dict(cache_len=48, prefill_chunk=16, max_prefill_per_step=2,
              block_size=8)
    ref = drain(ContinuousEngine(model, params, num_slots=4,
                                 kv_layout="paged", **kw), requests())
    for placement in ("replicated", "disagg"):
        fab = ServingFabric(model, params, ranks=n, placement=placement,
                            slots_per_rank=4, **kw)
        try:
            out = drain(fab, requests())
            assert all(np.array_equal(a, b) for a, b in zip(ref, out)), \
                placement
            for i, w in enumerate(fab.workers):
                want = {jax.devices()[i]}
                assert w.device == jax.devices()[i]
                assert w.engine.params["embed"].devices() == want
                for leaf in jax.tree_util.tree_leaves(w.engine.kv.buffers):
                    assert leaf.devices() == want, (placement, i)
            if placement == "disagg":
                assert fab.transport.n_migrations == len(trace)
        finally:
            fab.close()
    print("CASE-OK")


def case_chip_smoke_four():
    """chip_smoke.py's four-chip phase at the smoke config: the replicated
    fabric, rank i on device i, token-identical to one paged engine."""
    import chip_smoke
    r = chip_smoke.serve_four_chips(smoke=True)
    assert r["identical_requests"] == r["requests"] == chip_smoke.ROWS
    assert len({row["device"] for row in r["per_rank"]}) == 4
    print("CASE-OK")


CASES = {k[5:]: v for k, v in list(globals().items())
         if k.startswith("case_")}


def main():
    name = sys.argv[1]
    CASES[name]()


if __name__ == "__main__":
    main()
