"""Multi-device behavior on 8 forced host devices.

These tests need a different XLA device count than the rest of the suite,
so each runs in a subprocess with its own XLA_FLAGS (the conftest/session
stays at 1 device, as required).
"""
import os
import subprocess
import sys
import textwrap


_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert jax.device_count() == 8, jax.device_count()
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_distributed_bloom_or_allreduce_matches_host():
    _run("""
    from repro.core.distributed import (make_distributed_transfer,
                                        shard_table_arrays)
    from repro.core import bloom
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    bkeys = rng.integers(0, 10**6, 4096).astype(np.int64)
    pkeys = np.concatenate([bkeys[:2048],
                            rng.integers(2*10**6, 3*10**6, 2048)
                            .astype(np.int64)])
    blo, bhi, bm = shard_table_arrays(bkeys, mesh)
    plo, phi, pm = shard_table_arrays(pkeys, mesh)
    nblocks = bloom.blocks_for(len(bkeys))
    exp = np.isin(pkeys, bkeys)
    for tree in (False, True):
        fn = make_distributed_transfer(mesh, nblocks=nblocks,
                                       tree_or=tree)
        got = np.asarray(fn(blo, bhi, bm, plo, phi, pm))[:len(pkeys)]
        assert got[exp].all(), tree            # no false negatives
        assert (got & ~exp).mean() < 0.02      # bounded fp
    # gather-OR and tree-OR agree exactly
    a = np.asarray(make_distributed_transfer(mesh, nblocks=nblocks)(
        blo, bhi, bm, plo, phi, pm))
    b = np.asarray(make_distributed_transfer(mesh, nblocks=nblocks,
                                             tree_or=True)(
        blo, bhi, bm, plo, phi, pm))
    np.testing.assert_array_equal(a, b)
    print("distributed bloom OK (gather + tree OR)")
    """)


def test_distributed_semi_join_exact():
    _run("""
    from repro.core.distributed import (distributed_semi_join,
                                        shard_table_arrays)
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    b = rng.integers(0, 10**6, 4096).astype(np.int32)
    p = np.concatenate([b[:1000],
        rng.integers(2*10**6, 3*10**6, 3096).astype(np.int32)])
    sh = NamedSharding(mesh, P("data"))
    fn = distributed_semi_join(mesh)
    bm = jnp.ones(len(b), bool); pm = jnp.ones(len(p), bool)
    got = np.asarray(fn(jax.device_put(jnp.asarray(b), sh),
                        jax.device_put(bm, sh),
                        jax.device_put(jnp.asarray(p), sh),
                        jax.device_put(pm, sh)))
    np.testing.assert_array_equal(got, np.isin(p, b))
    print("distributed semijoin OK")
    """)


def test_mesh_exchange_all_to_all_matches_simulated():
    """The device exchange (lax.all_to_all / all_gather inside
    shard_map) and its numpy mirror deliver identical blocks, and the
    join strategies built on them reproduce the single-host reference
    bit for bit on real (forced-host) devices."""
    _run("""
    from repro.core.engine_join import NumpyJoinEngine, \\
        sorted_join_indices
    from repro.core.engine_join_dist import (MeshExchange,
        SimulatedExchange, broadcast_join_indices, shuffle_join_indices)
    dev = MeshExchange()
    assert dev.device_backed and dev.nshards == 8, dev.nshards
    sim = SimulatedExchange(8)
    rng = np.random.default_rng(3)
    # raw exchange equivalence on ragged uint32 blocks
    blocks = [[rng.integers(0, 2**32, (int(rng.integers(0, 9)), 3),
                            dtype=np.uint32)
               for _ in range(8)] for _ in range(8)]
    got = dev.all_to_all(blocks)
    exp = sim.all_to_all(blocks)
    for t in range(8):
        np.testing.assert_array_equal(got[t], exp[t], err_msg=str(t))
    shards = [rng.integers(0, 2**32, (int(rng.integers(0, 7)), 2),
                           dtype=np.uint32) for _ in range(8)]
    np.testing.assert_array_equal(dev.all_gather(shards),
                                  sim.all_gather(shards))
    # strategy-level bit-exactness over the device exchange
    eng = NumpyJoinEngine()
    for nb, npr in ((4096, 20000), (17, 5000), (5000, 33)):
        bk = rng.integers(-3, nb // 2 + 1, nb).astype(np.int64)
        pk = rng.integers(-3, nb // 2 + 9, npr).astype(np.int64)
        for how in ("inner", "left", "semi", "anti"):
            eb, ep = sorted_join_indices(bk, pk, how)
            for fn in (lambda: shuffle_join_indices(bk, pk, how, dev),
                       lambda: broadcast_join_indices(bk, pk, how, dev,
                                                      eng)):
                gb, gp, _ = fn()
                np.testing.assert_array_equal(gb, eb, err_msg=how)
                np.testing.assert_array_equal(gp, ep, err_msg=how)
    print("mesh exchange OK")
    """)


def test_distributed_engine_tpch_on_devices():
    """End-to-end: all 20 TPC-H queries through
    Executor(engine="distributed") with the device-backed exchange on 8
    forced host devices, bit-exact vs the single-host oracle."""
    _run("""
    from repro.relational import Executor
    from repro.tpch import QUERIES, build_query, generate
    cat = generate(sf=0.01, seed=7)
    for qn in sorted(QUERIES):
        ref, _ = Executor(cat).execute(build_query(qn, sf=0.01))
        got, st = Executor(cat, engine="distributed").execute(
            build_query(qn, sf=0.01))
        assert st.dist.device_backed and st.dist.nshards == 8, st.dist
        assert ref.names == got.names and len(ref) == len(got), qn
        for n in ref.names:
            va = ref[n].valid if ref[n].valid is not None \\
                else np.ones(len(ref), bool)
            vb = got[n].valid if got[n].valid is not None \\
                else np.ones(len(got), bool)
            np.testing.assert_array_equal(va, vb, err_msg=(qn, n))
            np.testing.assert_array_equal(ref[n].data[va],
                                          got[n].data[vb],
                                          err_msg=(qn, n))
    print("TPC-H distributed-on-devices OK")
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    _run("""
    from repro.configs import get_smoke_config
    from repro.models.model import Model, Batch
    from repro.parallel import sharding as S
    from repro.train import optim as O
    from repro.train.step import TrainConfig, build_train_step
    from repro.launch.mesh import make_test_mesh
    import dataclasses

    cfg = get_smoke_config("qwen1.5-4b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = O.AdamW(lr=lambda s: jnp.float32(1e-3))
    state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    batch = Batch(tokens, jnp.roll(tokens, -1, 1), None)
    step = build_train_step(model, opt, TrainConfig(microbatches=2))
    # single-device reference
    p1, s1, m1 = jax.jit(step)(params, state, batch)

    mesh = make_test_mesh((4, 2), ("data", "model"))
    with jax.set_mesh(mesh):
        psh = S.param_shardings(cfg, mesh)
        params_d = jax.device_put(params, psh)
        state_d = jax.device_put(
            state, O.AdamWState(NamedSharding(mesh, P()),
                                psh, psh))
        bsh = NamedSharding(mesh, S.batch_spec(mesh, 8))
        batch_d = Batch(jax.device_put(batch.tokens, bsh),
                        jax.device_put(batch.targets, bsh), None)
        p2, s2, m2 = jax.jit(step)(params_d, state_d, batch_d)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2, \
        (float(m1["loss"]), float(m2["loss"]))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-2)
    print("sharded step matches single-device")
    """)


def test_compressed_psum_int8_error_feedback():
    _run("""
    from repro.parallel.compress import compressed_psum_int8
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g = rng.normal(size=(8, 256)).astype(np.float32)
    sh = NamedSharding(mesh, P("data"))

    def f(gs, err):
        return compressed_psum_int8(gs, "data", err)

    fn = jax.jit(jax.shard_map(f, mesh=mesh,
                               in_specs=(P("data"), P("data")),
                               out_specs=(P("data"), P("data"))))
    err = jnp.zeros((8, 256), jnp.float32)
    mean, new_err = fn(jax.device_put(jnp.asarray(g), sh),
                       jax.device_put(err, sh))
    exact = g.mean(axis=0)
    got = np.asarray(mean)[0]
    assert np.abs(got - exact).max() < 0.05, np.abs(got - exact).max()
    # error feedback: residual equals what quantization dropped
    assert np.isfinite(np.asarray(new_err)).all()
    print("compressed psum OK")
    """)


def test_elastic_training_resume_on_new_mesh(tmp_path):
    """The full elastic story: train on one device, checkpoint, then a
    'restarted job' resumes the same run sharded over a (4,2) mesh and
    keeps training — loss trajectory continues without reset."""
    _run(f"""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_smoke_config
    from repro.ft import FaultTolerantTrainer
    from repro.models.model import Batch, Model
    from repro.parallel import sharding as S
    from repro.train import optim as O
    from repro.train.step import TrainConfig, build_train_step
    from repro.launch.mesh import make_test_mesh

    cfg = get_smoke_config("qwen1.5-4b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = O.AdamW(lr=lambda s: jnp.float32(1e-3))
    step = jax.jit(build_train_step(model, opt, TrainConfig()))
    mgr = CheckpointManager(r"{tmp_path}", keep=2, async_save=False)
    trainer = FaultTolerantTrainer(step, mgr, save_every=100)

    def batches():
        rng = np.random.default_rng(0)
        while True:
            t0 = rng.integers(0, 17, (8, 1))
            toks = ((t0 + np.arange(32)[None, :]) % 17).astype(np.int32)
            t = jnp.asarray(toks)
            yield Batch(t, jnp.roll(t, -1, 1), None)

    losses = []
    state = trainer.resume_or_init(params, opt.init(params))
    out = trainer.run(state, batches(),
                      max_steps=8,
                      on_metrics=lambda i, m: losses.append(m["loss"]))
    assert out["step"] == 8

    # "cluster grew": resume onto a (4,2) mesh with sharded params
    mesh = make_test_mesh((4, 2), ("data", "model"))
    with jax.set_mesh(mesh):
        psh = S.param_shardings(cfg, mesh)
        osh = O.AdamWState(NamedSharding(mesh, P()), psh, psh)
        trainer2 = FaultTolerantTrainer(step, mgr, save_every=100)
        step_n, restored = mgr.restore_latest(
            {{"params": params, "opt": opt.init(params)}},
            {{"params": psh, "opt": osh}})
        assert step_n == 8
        state2 = {{"params": restored["params"],
                   "opt": restored["opt"], "step": step_n}}
        losses2 = []
        out2 = trainer2.run(state2, batches(), max_steps=16,
                            on_metrics=lambda i, m:
                            losses2.append(m["loss"]))
    assert out2["step"] == 16
    # training continued (no loss reset to init ~ln(512)=6.2)
    assert losses2[0] < losses[0], (losses[0], losses2[0])
    print("elastic training resume OK:",
          round(losses[0], 3), "->", round(losses2[-1], 3))
    """)


def test_elastic_reshard_restore(tmp_path):
    _run(f"""
    from repro.checkpoint import CheckpointManager
    from repro.launch.mesh import make_test_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
             "s": jnp.int32(7)}}
    mgr = CheckpointManager(r"{tmp_path}", keep=2, async_save=False)
    mgr.save(5, tree)

    # restore onto a (4,2) mesh with w sharded both ways — "the cluster
    # changed shape between runs"
    mesh = make_test_mesh((4, 2), ("data", "model"))
    sh = {{"w": NamedSharding(mesh, P("data", "model")),
          "s": NamedSharding(mesh, P())}}
    step, out = mgr.restore_latest(tree, sh)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding.spec == P("data", "model")
    print("elastic reshard OK")
    """)


def test_mesh_exchange_ships_validity_planes():
    """Nullable join sides over the *device* exchange: the validity
    plane travels as a 4th uint32 plane through lax.all_to_all (and a
    3rd through all_gather) and both strategies reproduce the host
    compact-then-join oracle bit for bit (DESIGN §10)."""
    _run("""
    from repro.core.engine_join import NumpyJoinEngine
    from repro.core.engine_join_dist import (MeshExchange,
        broadcast_join_indices, shuffle_join_indices)
    dev = MeshExchange()
    assert dev.device_backed and dev.nshards == 8, dev.nshards
    host = NumpyJoinEngine()
    rng = np.random.default_rng(11)
    for nb, npr in ((4096, 20000), (29, 5000)):
        bk = rng.integers(0, nb // 2 + 1, nb).astype(np.int64)
        pk = rng.integers(0, nb // 2 + 9, npr).astype(np.int64)
        bv = rng.random(nb) > 0.25
        pv = rng.random(npr) > 0.25
        for how in ("inner", "left", "semi", "anti"):
            eb, ep = host.join_indices_valid(bk, pk, how=how,
                                             build_valid=bv,
                                             probe_valid=pv)
            for fn in (lambda: shuffle_join_indices(
                           bk, pk, how, dev, build_valid=bv,
                           probe_valid=pv),
                       lambda: broadcast_join_indices(
                           bk, pk, how, dev, host, build_valid=bv,
                           probe_valid=pv)):
                gb, gp, wire = fn()
                assert wire > 0
                np.testing.assert_array_equal(gb, eb, err_msg=how)
                np.testing.assert_array_equal(gp, ep, err_msg=how)
    print("mesh exchange validity planes OK")
    """)


def test_broadcast_join_runs_each_shard_on_its_device():
    """A device-backed broadcast join with a device-resident local
    engine: the exchange places shard blocks on every mesh device, each
    shard's local join runs under its own device's default-device scope
    (`MeshExchange.placement`, `DistStats.local_devices`), and the
    gathered indices are md5-equal to the single-host join."""
    _run("""
    import hashlib
    from repro.core.engine_join import NumpyJoinEngine, get_join_engine
    from repro.core.engine_join_dist import DistributedJoinEngine

    def md5(b, p):
        h = hashlib.md5()
        for a in (b, p):
            h.update(np.asarray(a, np.int64).tobytes())
        return h.hexdigest()

    rng = np.random.default_rng(3)
    bk = rng.integers(0, 500, 300).astype(np.int64)
    pk = rng.integers(0, 600, 40000).astype(np.int64)
    host = NumpyJoinEngine()
    for p in (4, 8):
        eng = DistributedJoinEngine(nshards=p, local_backend="jax",
                                    device=True)
        eng.local = get_join_engine("jax", device_resident=True)
        for how in ("inner", "left", "semi", "anti"):
            got = eng.join_indices(bk, pk, how=how)
            assert md5(*got) == md5(*host.join_indices(bk, pk, how)), how
        assert {j.strategy for j in eng.stats.joins} == {"broadcast"}
        mesh = {d.id for d in eng.exchange.devices}
        assert len(mesh) == p, mesh
        assert eng.exchange.placement == mesh, eng.exchange.placement
        assert eng.stats.local_devices == mesh, eng.stats.local_devices
    print("broadcast join placement OK")
    """)
