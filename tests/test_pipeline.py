"""GPipe pipeline-parallel tests on the virtual 8-device mesh: output
parity with sequential stage application, gradients through the
schedule, and composition with data parallelism (dp x pp)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.pipeline import gpipe


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _sequential(stacked, micro):
    out = []
    for m in range(micro.shape[0]):
        h = micro[m]
        for s in range(stacked["w"].shape[0]):
            h = _stage_fn({"w": stacked["w"][s], "b": stacked["b"][s]}, h)
        out.append(h)
    return jnp.stack(out)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_gpipe_matches_sequential():
    mesh = make_mesh({"pp": 4})
    rng = np.random.RandomState(0)
    d, mb, n_micro = 8, 4, 6
    stacked = {
        "w": jnp.asarray(rng.randn(4, d, d), jnp.float32) * 0.3,
        "b": jnp.asarray(rng.randn(4, d), jnp.float32) * 0.1,
    }
    micro = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    piped = gpipe(_stage_fn, mesh, checkpoint_stages=False)
    got = jax.jit(piped)(stacked, micro)
    want = _sequential(stacked, micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_gpipe_grads_and_dp():
    mesh = make_mesh({"dp": 2, "pp": 4})
    rng = np.random.RandomState(1)
    d, mb, n_micro = 8, 4, 5
    stacked = {
        "w": jnp.asarray(rng.randn(4, d, d), jnp.float32) * 0.3,
        "b": jnp.zeros((4, d), jnp.float32),
    }
    micro = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    piped = gpipe(_stage_fn, mesh)

    def loss_piped(p):
        return jnp.mean((piped(p, micro) - tgt) ** 2)

    def loss_seq(p):
        return jnp.mean((_sequential(p, micro) - tgt) ** 2)

    lp, gp = jax.jit(jax.value_and_grad(loss_piped))(stacked)
    ls, gs = jax.value_and_grad(loss_seq)(stacked)
    assert abs(float(lp) - float(ls)) < 1e-5
    np.testing.assert_allclose(np.asarray(gp["w"]), np.asarray(gs["w"]),
                               rtol=1e-4, atol=1e-5)

    # a few SGD steps through the pipeline reduce the loss
    p = stacked
    for _ in range(10):
        l, g = jax.jit(jax.value_and_grad(loss_piped))(p)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.5 * b, p, g)
    assert float(loss_piped(p)) < float(lp) * 0.85


def _loss_fn(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def _direct_loss(stacked, micro, tgt):
    total = 0.0
    for m in range(micro.shape[0]):
        h = micro[m]
        for s in range(stacked["w"].shape[0]):
            h = _stage_fn({"w": stacked["w"][s], "b": stacked["b"][s]}, h)
        total = total + _loss_fn(h, tgt[m])
    return total / micro.shape[0]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_one_f_one_b_matches_autodiff():
    """The manually-scheduled 1F1B loss AND grads must equal plain
    jax.grad through the sequential model."""
    from paddle_tpu.parallel.pipeline import one_f_one_b
    mesh = make_mesh({"pp": 4})
    rng = np.random.RandomState(3)
    d, mb, n_micro = 8, 4, 6
    stacked = {
        "w": jnp.asarray(rng.randn(4, d, d), jnp.float32) * 0.3,
        "b": jnp.asarray(rng.randn(4, d), jnp.float32) * 0.1,
    }
    micro = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)

    step = one_f_one_b(_stage_fn, _loss_fn, mesh)
    loss, grads = jax.jit(step)(stacked, micro, tgt)

    want_loss, want_grads = jax.value_and_grad(
        lambda p: _direct_loss(p, micro, tgt))(stacked)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(want_grads["w"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["b"]),
                               np.asarray(want_grads["b"]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_one_f_one_b_dp_and_training():
    """dp2 x pp4: grads average over dp shards; SGD on the schedule's
    own grads reduces the loss."""
    from paddle_tpu.parallel.pipeline import one_f_one_b
    mesh = make_mesh({"dp": 2, "pp": 4})
    rng = np.random.RandomState(4)
    d, mb, n_micro = 8, 4, 5
    p = {
        "w": jnp.asarray(rng.randn(4, d, d), jnp.float32) * 0.3,
        "b": jnp.zeros((4, d), jnp.float32),
    }
    micro = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)

    step = jax.jit(one_f_one_b(_stage_fn, _loss_fn, mesh))
    loss0, _ = step(p, micro, tgt)
    want_loss = _direct_loss(p, micro, tgt)
    assert abs(float(loss0) - float(want_loss)) < 1e-5

    # one step in flight at a time. Dispatched without waiting, forty
    # steps queue on the eight device threads, and once in some eighty
    # runs under load seven threads meet in a step's collective permute
    # while the eighth never comes: after 40 s XLA's CPU backend ends the
    # PROCESS ("Termination timeout ... exceeded", `Fatal Python error:
    # Aborted`) and the xdist worker's other tests with it. Waiting for
    # each step, 150 runs under the same load all ended.
    for _ in range(40):
        loss, grads = jax.block_until_ready(step(p, micro, tgt))
        p = jax.tree_util.tree_map(lambda a, g: a - 0.4 * g, p, grads)
    assert float(loss) < float(loss0) * 0.7, (float(loss0), float(loss))


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_one_f_one_b_loss_params_and_dx():
    """Extended mode: head/loss params get their own grads (accumulated
    at the last stage) and dx (d loss / d micro inputs) comes back for
    the upstream embedding — all equal to plain autodiff."""
    from paddle_tpu.parallel.pipeline import one_f_one_b
    mesh = make_mesh({"dp": 2, "pp": 4})
    rng = np.random.RandomState(5)
    d, mb, n_micro = 8, 4, 6
    stacked = {
        "w": jnp.asarray(rng.randn(4, d, d), jnp.float32) * 0.3,
        "b": jnp.asarray(rng.randn(4, d), jnp.float32) * 0.1,
    }
    lparams = {"head": jnp.asarray(rng.randn(d, 3), jnp.float32) * 0.5}
    micro = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, 3, (n_micro, mb)))

    def loss_fn(lp, y, t):
        logits = y @ lp["head"]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
        return jnp.mean(lse - picked)

    step = one_f_one_b(_stage_fn, loss_fn, mesh, loss_params=True,
                       return_dx=True)
    loss, grads, lgrads, dx = jax.jit(step)(stacked, lparams, micro,
                                            tgt)

    def direct(p, lp, mx):
        total = 0.0
        for m in range(mx.shape[0]):
            h = mx[m]
            for s in range(p["w"].shape[0]):
                h = _stage_fn({"w": p["w"][s], "b": p["b"][s]}, h)
            total = total + loss_fn(lp, h, tgt[m])
        return total / mx.shape[0]

    want_loss, (want_g, want_lg, want_dx) = jax.value_and_grad(
        direct, argnums=(0, 1, 2))(stacked, lparams, micro)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(want_g["w"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lgrads["head"]),
                               np.asarray(want_lg["head"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want_dx),
                               rtol=1e-4, atol=1e-5)
