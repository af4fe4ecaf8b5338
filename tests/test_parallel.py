"""ParallelExecutor / sharding transpiler tests on the 8-device virtual
CPU mesh (conftest forces xla_force_host_platform_device_count=8).

Mirrors the reference's ParallelExecutor unittests
(test_parallel_executor*.py): same model trained single- vs multi-device
should converge identically-ish; tensor-parallel sharding must produce
the same numbers as replicated execution.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.parallel import make_mesh, ShardingTranspiler


def build_model():
    img = fluid.layers.data(name="img", shape=[32], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(img, size=64, act="relu")
    h = fluid.layers.fc(h, size=64, act="relu")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    return loss


def batch(seed, n=32):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 4, (n, 1)).astype(np.int64)
    x = (np.eye(4, 32)[y[:, 0]] * 3 + rng.randn(n, 32) * 0.3).astype(
        np.float32)
    return x, y


def test_eight_devices_present():
    assert len(jax.devices()) == 8


def test_mesh_larger_than_the_default_backend_is_an_error():
    """A mesh is laid over jax.devices() and nothing else: no other
    backend's devices stand in for missing ones."""
    assert make_mesh({"dp": -1}).size() == 8
    with pytest.raises(ValueError, match="cannot be laid out over 8"):
        make_mesh({"dp": 4, "tp": 4})


def test_data_parallel_trains():
    loss = build_model()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    pe = fluid.ParallelExecutor(loss_name=loss.name,
                                mesh=make_mesh({"dp": 8}))
    assert pe.device_count == 8
    losses = []
    for step in range(20):
        x, y = batch(step)
        out = pe.run(feed={"img": x, "label": y}, fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(())))
    assert losses[-1] < losses[0] * 0.6, losses


def test_data_parallel_matches_single_device():
    """Same seed, same data → dp-8 must track single-device closely."""
    with fluid.unique_name.guard():
        p1 = fluid.Program()
        s1 = fluid.Program()
        with fluid.program_guard(p1, s1):
            loss1 = build_model()
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss1)
    with fluid.unique_name.guard():
        p2 = fluid.Program()
        s2 = fluid.Program()
        with fluid.program_guard(p2, s2):
            loss2 = build_model()
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss2)
    p1.random_seed = s1.random_seed = 5
    p2.random_seed = s2.random_seed = 5

    scope1, scope2 = fluid.Scope(), fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope1):
        exe.run(s1)
    with fluid.scope_guard(scope2):
        fluid.Executor(fluid.CPUPlace()).run(s2)
        # copy identical init from scope1 so both start equal; materialize
        # to numpy — the train jit donates state buffers, so sharing jax
        # arrays across scopes would invalidate scope2's copies
        for k in list(scope1.vars):
            scope2.set(k, np.asarray(scope1.find_var(k)))

    l1s, l2s = [], []
    with fluid.scope_guard(scope1):
        for step in range(5):
            x, y = batch(step)
            out = exe.run(p1, feed={"img": x, "label": y},
                          fetch_list=[loss1.name])
            l1s.append(float(np.asarray(out[0]).reshape(())))
    pe = fluid.ParallelExecutor(loss_name=loss2.name, main_program=p2,
                                scope=scope2, mesh=make_mesh({"dp": 8}))
    for step in range(5):
        x, y = batch(step)
        out = pe.run(feed={"img": x, "label": y}, fetch_list=[loss2.name])
        l2s.append(float(np.asarray(out[0]).reshape(())))
    np.testing.assert_allclose(l1s, l2s, rtol=2e-3, atol=2e-4)


def test_tensor_parallel_matches_replicated():
    loss = build_model()
    fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)  # lr 0: pure fwd
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    x, y = batch(0)
    ref = exe.run(fluid.default_main_program(),
                  feed={"img": x, "label": y}, fetch_list=[loss.name])

    ShardingTranspiler().tensor_parallel(axis="tp")
    pe = fluid.ParallelExecutor(loss_name=loss.name,
                                mesh=make_mesh({"tp": 8}))
    out = pe.run(feed={"img": x, "label": y}, fetch_list=[loss.name])
    np.testing.assert_allclose(np.asarray(ref[0]).reshape(()),
                               np.asarray(out[0]).reshape(()), rtol=1e-4)


def test_zero_optimizer_sharding():
    loss = build_model()
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    ShardingTranspiler().shard_optimizer(axis="dp")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss.name,
                                mesh=make_mesh({"dp": 8}))
    losses = []
    for step in range(10):
        x, y = batch(step)
        out = pe.run(feed={"img": x, "label": y}, fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(())))
    assert losses[-1] < losses[0], losses


def test_distribute_transpiler_compat():
    loss = build_model()
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=0, trainers=8)
    prog = t.get_trainer_program()
    assert prog is fluid.default_main_program()
    with pytest.raises(NotImplementedError):
        t.get_pserver_program("127.0.0.1:6174")


def test_quantized_all_reduce_close_to_exact():
    """EQuARX-style int8 gradient allreduce (parallel/collectives.py):
    ~1e-2 relative error vs the exact psum on a dp mesh."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import parallel
    from paddle_tpu.parallel import collectives as C

    mesh = parallel.DeviceMesh({"dp": 8})
    rng = np.random.RandomState(0)
    grads = rng.randn(8, 64).astype(np.float32)

    @jax.jit
    def reduce_both(g):
        def f(gs):
            return (C.quantized_all_reduce(gs[0], "dp"),
                    C.all_reduce(gs[0], "dp"))
        return shard_map(f, mesh=mesh.mesh, in_specs=P("dp", None),
                         out_specs=(P(), P()))(g)

    approx, exact = reduce_both(grads)
    approx, exact = np.asarray(approx), np.asarray(exact)
    rel = np.abs(approx - exact).max() / np.abs(exact).max()
    assert rel < 2e-2, rel
    # and it is deterministic/bit-stable across calls
    a2, _ = reduce_both(grads)
    np.testing.assert_array_equal(approx, np.asarray(a2))


def test_compiled_stats_reports_collectives():
    """The sharded executable's optimized HLO must carry the GSPMD
    collectives the mesh implies: dp gradient sync appears as
    all-reduce (or its reduce-scatter+all-gather decomposition) —
    the compile-time artifact behind SURVEY §6's allreduce story."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = build_model()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    mesh = make_mesh({"dp": 8})
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                scope=scope, mesh=mesh)
    x, y = batch(0, 32)
    stats = pe.compiled_stats([loss.name], feed={"img": x, "label": y})
    assert stats["mesh"] == {"dp": 8}
    assert stats["n_kernels"] > 0
    coll = stats["collectives"]
    # dp-8 grad sync: at least one all-reduce-family op must exist
    assert sum(coll.get(k, 0) for k in
               ("all-reduce", "reduce-scatter", "all-gather")) > 0, coll
    # and a replicated single-axis mesh of ONE device inserts none
    mesh1 = make_mesh({"dp": 1})
    pe1 = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                 scope=scope, mesh=mesh1)
    stats1 = pe1.compiled_stats([loss.name],
                                feed={"img": x[:4], "label": y[:4]})
    assert not stats1["collectives"], stats1["collectives"]


def test_compiled_stats_tp_mesh_gathers():
    """Tensor-parallel shardings (ShardingTranspiler) must induce
    collectives on the activation path too (all-gather / all-reduce
    between the column- and row-parallel fc pair)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = build_model()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    mesh = make_mesh({"dp": 2, "tp": 4})
    ShardingTranspiler().tensor_parallel(main, axis="tp")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                scope=scope, mesh=mesh)
    x, y = batch(1, 32)
    stats = pe.compiled_stats([loss.name], feed={"img": x, "label": y})
    coll = stats["collectives"]
    assert sum(coll.values()) >= 2, coll


# ---------------------------------------------------------------------------
# convnet (conv + batch_norm) under the mesh — the reference
# ParallelExecutor's headline usage is data-parallel ResNet/VGG
# (benchmark/fluid/fluid_benchmark.py:235). BN is the op whose dp
# semantics differ between executors: the reference computes PER-REPLICA
# batch statistics (each device normalizes with its local sub-batch),
# while under GSPMD the batch-axis mean/variance reduces become
# cross-replica collectives, so our dp BN statistics are GLOBAL-BATCH
# (SyncBN semantics). With the same full batch, dp-8 must therefore
# track the single-device trajectory exactly — pinned here.
# ---------------------------------------------------------------------------


def build_conv_bn_model():
    img = fluid.layers.data(name="img", shape=[3, 16, 16],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.conv2d(img, num_filters=8, filter_size=3,
                            padding=1, bias_attr=False)
    h = fluid.layers.batch_norm(h, act="relu")
    h = fluid.layers.pool2d(h, pool_size=2, pool_stride=2,
                            pool_type="max")
    h = fluid.layers.conv2d(h, num_filters=16, filter_size=3,
                            padding=1, bias_attr=False)
    h = fluid.layers.batch_norm(h, act="relu")
    h = fluid.layers.pool2d(h, global_pooling=True, pool_type="avg")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    return loss


def conv_batch(seed, n=32):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 4, (n, 1)).astype(np.int64)
    x = rng.randn(n, 3, 16, 16).astype(np.float32) * 0.5
    # class-dependent mean so the model has something to learn
    x += y[:, :, None, None] * 0.3
    return x, y


def test_conv_bn_dp_matches_single_device():
    """dp-8 conv+BN == single device: GSPMD's cross-replica BN
    reduction makes the dp batch statistics global-batch, so the
    trajectories must agree to float tolerance (NOT just 'close' —
    this is the semantic pin for SyncBN-style dp BN)."""
    with fluid.unique_name.guard():
        p1, s1 = fluid.Program(), fluid.Program()
        with fluid.program_guard(p1, s1):
            loss1 = build_conv_bn_model()
            fluid.optimizer.Momentum(learning_rate=0.05,
                                     momentum=0.9).minimize(loss1)
    with fluid.unique_name.guard():
        p2, s2 = fluid.Program(), fluid.Program()
        with fluid.program_guard(p2, s2):
            loss2 = build_conv_bn_model()
            fluid.optimizer.Momentum(learning_rate=0.05,
                                     momentum=0.9).minimize(loss2)
    p1.random_seed = s1.random_seed = 7
    p2.random_seed = s2.random_seed = 7

    scope1, scope2 = fluid.Scope(), fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope1):
        exe.run(s1)
    with fluid.scope_guard(scope2):
        exe.run(s2)
        for k in list(scope1.vars):
            scope2.set(k, np.asarray(scope1.find_var(k)))

    l1s, l2s = [], []
    with fluid.scope_guard(scope1):
        for step in range(4):
            x, y = conv_batch(step)
            out = exe.run(p1, feed={"img": x, "label": y},
                          fetch_list=[loss1.name])
            l1s.append(float(np.asarray(out[0]).reshape(())))
    pe = fluid.ParallelExecutor(loss_name=loss2.name, main_program=p2,
                                scope=scope2, mesh=make_mesh({"dp": 8}))
    for step in range(4):
        x, y = conv_batch(step)
        out = pe.run(feed={"img": x, "label": y},
                     fetch_list=[loss2.name])
        l2s.append(float(np.asarray(out[0]).reshape(())))
    np.testing.assert_allclose(l1s, l2s, rtol=2e-4, atol=2e-5)
    assert l1s[-1] < l1s[0], l1s

    # the moving statistics the two executors accumulated must agree
    # too — the direct evidence that dp BN stats are global-batch, not
    # per-replica (per-replica stats would diverge from step 1: each
    # shard of conv_batch has a different class mix)
    bn_stats = [k for k in scope1.vars
                if "batch_norm" in k and ".global_" in k]
    assert bn_stats, list(scope1.vars)[:20]
    for k in bn_stats:
        np.testing.assert_allclose(
            np.asarray(scope1.find_var(k)),
            np.asarray(scope2.find_var(k)), rtol=2e-4, atol=2e-5)


def test_conv_bn_dp_trains():
    """dp-8 conv+BN training makes progress and inserts grad-sync
    collectives (the compile-time artifact for the reference's
    dp-ResNet headline config)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = build_conv_bn_model()
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                scope=scope, mesh=make_mesh({"dp": 8}))
    losses = []
    for step in range(12):
        x, y = conv_batch(step % 3)
        out = pe.run(feed={"img": x, "label": y},
                     fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(())))
    assert losses[-1] < losses[0] * 0.7, losses

    x, y = conv_batch(0)
    coll = pe.compiled_stats([loss.name],
                             feed={"img": x, "label": y})["collectives"]
    assert sum(coll.get(k, 0) for k in
               ("all-reduce", "reduce-scatter", "all-gather")) > 0, coll
