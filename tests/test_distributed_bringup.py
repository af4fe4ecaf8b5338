"""REAL 2-process ``jax.distributed`` bring-up (VERDICT next #4): the
mocked env-mapping tests in test_init_distributed.py prove the
argument plumbing; this one proves the rendezvous itself. Two
subprocesses — a coordinator and a worker, each given 4 virtual CPU
devices via --xla_force_host_platform_device_count — call the real
``paddle_tpu.parallel.mesh.init_distributed`` (no mocks; the fluid
PADDLE_TRAINER_* env contract carries the addresses, and
init_distributed enables gloo CPU collectives so multiprocess
programs actually run), build a DeviceMesh over the 2×4 = 8-device
GLOBAL mesh, and run one data-parallel step: per-shard loss + grad, a
psum-mean over the dp axis, one SGD update, and the post-update loss.
Both processes must agree with each other AND with the single-process
numpy reference over the full 8-row batch — loss parity, the actual
point of data parallelism.

Each shard derives its row deterministically from
``lax.axis_index("dp")``, so no cross-process array feeding is needed
and the reference is exact analytic numpy.
"""
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import json, os, sys
    pid = int(sys.argv[1])
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from paddle_tpu.parallel import mesh as mesh_mod

    n_global = mesh_mod.init_distributed()      # PADDLE_* env contract
    mesh = mesh_mod.make_mesh({"dp": -1})       # spans BOTH processes

    def step(_):
        i = jax.lax.axis_index("dp")            # 0..7 across the pod
        x = (jnp.arange(4, dtype=jnp.float32) + 4.0 * i) / 100.0
        # each shard's OWN copy of the weights: differentiating by a value
        # that is the same on every shard gives, under this JAX's
        # shard_map, the gradient already SUMMED over "dp" (the transpose
        # of the broadcast), and the pmean below would then average eight
        # copies of the sum: a step eight times the reference's (0.41580
        # where it has 0.49677). The step moved, not the pin.
        w = jax.lax.pcast(jnp.full((4,), 0.5, jnp.float32), "dp",
                          to="varying")

        def loss_fn(w):
            return (jnp.dot(x, w) - 1.0) ** 2

        loss, g = jax.value_and_grad(loss_fn)(w)
        gloss = jax.lax.pmean(loss, "dp")       # the dp collective
        w2 = w - 0.1 * jax.lax.pmean(g, "dp")   # one SGD step
        loss2 = jax.lax.pmean((jnp.dot(x, w2) - 1.0) ** 2, "dp")
        return gloss, loss2

    f = jax.jit(shard_map(step, mesh=mesh.mesh,
                          in_specs=PartitionSpec(),
                          out_specs=PartitionSpec()))
    l1, l2 = f(jnp.zeros(()))
    print(json.dumps({
        "pid": pid,
        "n_global": n_global,
        "n_local": jax.local_device_count(),
        "process_index": jax.process_index(),
        "loss": float(l1), "loss_after_step": float(l2),
    }), flush=True)
""")


def _reference():
    """Single-process numpy replay of the same dp step over all 8
    rows: the parity target."""
    x = (np.arange(32, dtype=np.float64).reshape(8, 4)) / 100.0
    w = np.full(4, 0.5)
    err = x @ w - 1.0
    loss = float(np.mean(err ** 2))
    grad = np.mean(2.0 * err[:, None] * x, axis=0)
    w2 = w - 0.1 * grad
    loss2 = float(np.mean((x @ w2 - 1.0) ** 2))
    return loss, loss2


def test_two_process_bringup_dp_step_loss_parity(tmp_path):
    with socket.socket() as s:                  # free rendezvous port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    child = tmp_path / "dist_child.py"
    child.write_text(_CHILD)

    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            # the fluid trainer env contract init_distributed consumes
            "PADDLE_TRAINER_ENDPOINTS":
                f"127.0.0.1:{port},127.0.0.1:{port + 1}",
            "PADDLE_TRAINERS": "2",
            "PADDLE_TRAINER_ID": str(pid),
            "PADDLE_TPU_CPU_COLLECTIVES": "gloo",
            "PYTHONPATH": _REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        env.pop("PADDLE_PSERVER_ENDPOINTS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(child), str(pid)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    records = {}
    fail = []
    for pid, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            fail.append(f"process {pid} timed out; stderr: {err[-500:]}")
            continue
        if proc.returncode != 0:
            fail.append(f"process {pid} rc={proc.returncode}; "
                        f"stderr: {err[-800:]}")
            continue
        for line in out.splitlines():
            if line.startswith("{"):
                records[pid] = json.loads(line)
    if fail:
        pytest.fail(" | ".join(fail))

    assert set(records) == {0, 1}
    for pid, rec in records.items():
        assert rec["n_global"] == 8, rec        # 2 procs x 4 devices
        assert rec["n_local"] == 4, rec
        assert rec["process_index"] == pid, rec
    # both processes computed the SAME global loss (the psum really
    # crossed processes: each holds only half the rows)
    assert records[0]["loss"] == pytest.approx(records[1]["loss"])
    assert records[0]["loss_after_step"] == pytest.approx(
        records[1]["loss_after_step"])
    # and it matches the single-process full-batch reference
    ref_loss, ref_loss2 = _reference()
    assert records[0]["loss"] == pytest.approx(ref_loss, rel=1e-5)
    assert records[0]["loss_after_step"] == pytest.approx(ref_loss2,
                                                          rel=1e-5)
    # the step moved the loss down (sanity that the update applied)
    assert ref_loss2 < ref_loss


# ---------------------------------------------------------------------------
# kill-and-resume drill: SIGKILL a worker mid-run, restart, converge
# ---------------------------------------------------------------------------

_RESUME_CHILD = textwrap.dedent("""
    import json, os, signal, sys
    pid = int(sys.argv[1])
    total_steps = int(sys.argv[2])
    ckpt_dir = sys.argv[3]
    die_after = int(sys.argv[4])        # worker self-SIGKILLs before
                                        # this step; -1 = run to the end
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel import collectives
    from paddle_tpu.resilience import checkpoint as ckpt

    mesh_mod.init_distributed()
    mesh = mesh_mod.make_mesh({"dp": -1})

    def step(w):
        i = jax.lax.axis_index("dp")            # 0..7 across the pod
        x = (jnp.arange(4, dtype=jnp.float32) + 4.0 * i) / 100.0

        def loss_fn(w):
            return (jnp.dot(x, w) - 1.0) ** 2

        # by each shard's own copy of the weights, as in the child above:
        # by the shared ``w`` the gradient comes summed over "dp" already
        loss, g = jax.value_and_grad(loss_fn)(
            jax.lax.pcast(w, "dp", to="varying"))
        # the satellite under test: whole-pytree dp grad sync
        synced = collectives.grad_tree_sync({"w": g}, "dp")
        w2 = w - 0.1 * synced["w"]
        return jax.lax.pmean(loss, "dp"), w2

    f = jax.jit(shard_map(step, mesh=mesh.mesh,
                          in_specs=PartitionSpec(),
                          out_specs=PartitionSpec()))

    # resume from the newest committed serial, or start fresh
    try:
        state, _m, start, _p = ckpt.load_latest_valid(ckpt_dir)
        w = jnp.asarray(state["w"])
    except FileNotFoundError:
        start, w = 0, jnp.full((4,), 0.5, jnp.float32)

    for s in range(start + 1, total_steps + 1):
        if pid != 0 and die_after >= 0 and s > die_after:
            os.kill(os.getpid(), signal.SIGKILL)   # a real kill -9
        loss, w = f(w)
        if pid == 0:
            # leader-writes: only trainer 0 commits (and prunes)
            ckpt.save_state(ckpt_dir, {"w": np.asarray(w)}, serial=s,
                            meta={"step": s})
        print(f"STEP {s} {float(loss):.8f}", flush=True)

    print(json.dumps({"pid": pid, "resumed_at": start,
                      "final_loss": float(loss),
                      "w": np.asarray(w).tolist()}), flush=True)
""")


def _resume_reference(total_steps):
    """Numpy replay of the uninterrupted 8-row dp run — the parity
    target for the crash-resumed fleet."""
    x = (np.arange(32, dtype=np.float64).reshape(8, 4)) / 100.0
    w = np.full(4, 0.5)
    losses = []
    for _ in range(total_steps):
        err = x @ w - 1.0
        losses.append(float(np.mean(err ** 2)))
        w = w - 0.1 * np.mean(2.0 * err[:, None] * x, axis=0)
    return losses, w, float(np.mean((x @ w - 1.0) ** 2))


def _launch_pair(child, port, ckpt_dir, total_steps, die_after):
    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PADDLE_TRAINER_ENDPOINTS":
                f"127.0.0.1:{port},127.0.0.1:{port + 1}",
            "PADDLE_TRAINERS": "2",
            "PADDLE_TRAINER_ID": str(pid),
            "PADDLE_TPU_CPU_COLLECTIVES": "gloo",
            "PYTHONPATH": _REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        env.pop("PADDLE_PSERVER_ENDPOINTS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(child), str(pid), str(total_steps),
             str(ckpt_dir), str(die_after if pid == 1 else -1)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    return procs


@pytest.mark.slow
def test_kill_and_resume_dp_training_loss_parity(tmp_path):
    """The training-side failure story for the REAL 2-process bringup:
    the worker subprocess takes an actual SIGKILL mid-run (between the
    committed step and the next collective), the stranded coordinator
    is reaped, and a fresh pair restarted from the same env + shared
    checkpoint dir resumes from the last committed serial and
    converges to numpy loss parity with an uninterrupted run."""
    from paddle_tpu.resilience import checkpoint as ckpt

    total_steps, die_after = 8, 3
    ckpt_dir = tmp_path / "ckpts"
    child = tmp_path / "resume_child.py"
    child.write_text(_RESUME_CHILD)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = _launch_pair(child, port, ckpt_dir, total_steps, die_after)
    # the worker kills itself before step die_after+1; the coordinator
    # is left stranded in that step's collective — reap it, as an
    # operator (or a supervisor) would
    try:
        procs[1].wait(timeout=180)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("worker never died — the drill did not run")
    assert procs[1].returncode != 0     # SIGKILL, not a clean exit
    try:
        procs[0].wait(timeout=30)
    except subprocess.TimeoutExpired:
        pass                            # stuck in the dead collective
    procs[0].kill()
    out0, _err0 = procs[0].communicate()

    # the committed tail survived the kill: serials 1..die_after, and
    # the leader's last STEP line agrees with the reference curve
    serials = ckpt.list_serials(str(ckpt_dir))
    assert serials, "no committed checkpoint survived the kill"
    assert max(serials) == die_after, (serials, out0)
    ref_losses, ref_w, ref_final = _resume_reference(total_steps)
    for line in out0.splitlines():
        if line.startswith("STEP "):
            _tag, s, loss = line.split()
            assert float(loss) == pytest.approx(
                ref_losses[int(s) - 1], rel=1e-5), line

    # restart BOTH processes from env on a fresh port: resume + finish
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port2 = s.getsockname()[1]
    procs = _launch_pair(child, port2, ckpt_dir, total_steps, -1)
    records = {}
    fail = []
    for pid, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            fail.append(f"resumed process {pid} timed out; "
                        f"stderr: {err[-500:]}")
            continue
        if proc.returncode != 0:
            fail.append(f"resumed process {pid} rc={proc.returncode}; "
                        f"stderr: {err[-800:]}")
            continue
        for line in out.splitlines():
            if line.startswith("{"):
                records[pid] = json.loads(line)
    if fail:
        pytest.fail(" | ".join(fail))

    assert set(records) == {0, 1}
    for rec in records.values():
        assert rec["resumed_at"] == die_after, rec
    # both processes agree, and the resumed run lands on the SAME
    # curve as the uninterrupted reference — the psum crossed
    # processes and no committed step was lost or replayed wrong
    assert records[0]["final_loss"] == pytest.approx(
        records[1]["final_loss"])
    # the last STEP's loss is evaluated BEFORE its update — compare
    # against the reference curve's last pre-update entry; the final
    # weights are the post-update ones
    assert records[0]["final_loss"] == pytest.approx(ref_losses[-1],
                                                     rel=1e-5)
    np.testing.assert_allclose(np.asarray(records[0]["w"]), ref_w,
                               rtol=1e-5)
    assert ref_final < ref_losses[0]    # it converged, not just ran
