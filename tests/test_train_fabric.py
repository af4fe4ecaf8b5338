"""Elastic fault-tolerant training fabric tier-1 suite
(cluster/train_fabric.py, cluster/train_worker.py).

What is pinned here:

* **determinism is world-size invariant** — fixed logical shards,
  shard-index-order reduction: the committed (serial, sha) sequence is
  bit-identical at world size 1 and 2, which is what makes elastic
  resize and crash-resume sha-deterministic at all;
* **every failure mode is typed and recoverable** — a worker crash
  mid-step, a straggler past the deadline, and a vanished RPC route
  each evict the worker (typed reason in the event log), the step
  retries at reduced world size, and NO committed step is lost; a
  healed partition rejoins within the readmit sweep and records
  ``last_recover_s``;
* **the commit barrier is leader-writes / followers-verify** — a
  follower re-hashes the broadcast state and refuses a sha it did not
  compute; the coordinator evicts on mismatch rather than laundering
  divergence;
* **coordinator crash is the constructor's problem** — SimulatedCrash
  (a BaseException — recovery code cannot swallow it), workers park,
  and a NEW coordinator over the same checkpoint dir resumes from the
  last committed serial to sha parity with an uninterrupted run;
* **the compiled tier scales up mid-run** — a ProgramGradTask
  replacement worker rebuilds the program from the wire spec and joins
  without moving the loss curve (the elastic-up gate);
* **ops plane** — per-worker rows (last_step, step-time percentiles,
  heartbeat age, evictions/rejoins) and ServingMetrics.merge(label=)
  namespacing so per-worker counters never collide.

All CPU, all loopback sockets, LinReg (pure numpy) except the one
compiled-tier test. The multi-process drill lives in
tools/trainbench.py --chaos (selfcheck stage 12).
"""
import tempfile
import time

import numpy as np
import pytest

from paddle_tpu.cluster.net import RemoteUnavailableError
from paddle_tpu.cluster.train_fabric import (LinRegTask,
                                             NoTrainWorkersError,
                                             ProgramGradTask,
                                             TrainCoordinator,
                                             TrainTaskError,
                                             WorkerClient,
                                             task_from_spec)
from paddle_tpu.cluster.train_worker import TrainWorkerServer
from paddle_tpu.resilience import faultinject
from paddle_tpu.resilience.checkpoint import state_sha
from paddle_tpu.resilience.faultinject import SimulatedCrash
from paddle_tpu.serving.health import ServiceUnavailableError

pytestmark = pytest.mark.cluster


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.disarm()
    yield
    faultinject.disarm()


def _fleet(tmp_path, n=2, seed=5, **kw):
    workers = [TrainWorkerServer() for _ in range(n)]
    kw.setdefault("step_deadline_s", 5.0)
    kw.setdefault("admit_deadline_s", 2.0)
    kw.setdefault("readmit_interval_s", 0.05)
    co = TrainCoordinator(LinRegTask(seed=seed),
                          [w.addr for w in workers],
                          str(tmp_path / "ckpts"),
                          commit_interval=5, n_shards=4, **kw)
    return co, workers


def _teardown(co, workers):
    co.close()
    for w in workers:
        w.close()


def _baseline(seed=5, steps=10):
    """Single-worker run: the sha/loss parity target for every drill."""
    d = tempfile.mkdtemp(prefix="trainfab_base_")
    w = TrainWorkerServer()
    co = TrainCoordinator(LinRegTask(seed=seed), [w.addr], d,
                          commit_interval=5, n_shards=4)
    co.run(steps)
    commits, losses = co.commits(), co.losses()
    _teardown(co, [w])
    return commits, losses


# ---------------------------------------------------------------------------
# task specs
# ---------------------------------------------------------------------------


def test_task_spec_roundtrip_and_typed_refusals():
    task = LinRegTask(dim=6, rows_per_shard=3, lr=0.2, seed=9)
    clone = task_from_spec(task.spec())
    assert isinstance(clone, LinRegTask)
    assert clone.spec() == task.spec()
    prog = task_from_spec(ProgramGradTask(seed=2).spec())
    assert isinstance(prog, ProgramGradTask)
    with pytest.raises(TrainTaskError):
        task_from_spec({"no": "kind"})
    with pytest.raises(TrainTaskError):
        task_from_spec({"kind": "warp-drive"})
    with pytest.raises(TrainTaskError):
        task_from_spec(None)


def test_linreg_task_grad_sums_are_deterministic():
    t = LinRegTask(seed=3)
    s = t.init_state()
    a = t.grad_sums(s, step=4, shard=2, n_shards=4)
    b = t.grad_sums(s, step=4, shard=2, n_shards=4)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1]["w"], b[1]["w"])
    # different shard / step → different data
    c = t.grad_sums(s, step=4, shard=3, n_shards=4)
    assert a[0] != c[0]


# ---------------------------------------------------------------------------
# determinism + elasticity
# ---------------------------------------------------------------------------


def test_commits_are_world_size_invariant(tmp_path):
    base, base_losses = _baseline()
    co, ws = _fleet(tmp_path, n=2)
    co.run(10)
    assert co.commits() == base
    assert co.losses() == pytest.approx(base_losses)
    _teardown(co, ws)


def test_shard_assignment_is_deterministic_round_robin(tmp_path):
    co, ws = _fleet(tmp_path, n=2)
    co.run(1)                           # admission happens lazily
    live = co.live_workers()
    assert len(live) == 2
    assignment = co._assignment(live)
    flat = sorted(s for shards in assignment.values() for s in shards)
    assert flat == list(range(co.n_shards))
    # name-sorted order, round-robin: worker order is by name, not by
    # admit order, so reconnection order can never change the split
    names = sorted(c.name for c in live)
    by_name = {c.name: shards for c, shards in assignment.items()}
    assert by_name[names[0]] == [0, 2]
    assert by_name[names[1]] == [1, 3]
    _teardown(co, ws)


def test_worker_crash_evicts_retries_and_loses_nothing(tmp_path):
    base, _ = _baseline()
    co, ws = _fleet(tmp_path, n=2)
    co.run(2)
    faultinject.arm("trainer_crash_at_step", at=0)
    co.run(8)
    assert co.commits() == base, "a committed step was lost"
    assert co.evictions_total == 1
    events = co.events()
    assert [e["kind"] for e in events] == ["evicted"]
    assert events[0]["step"] > 2
    _teardown(co, ws)


def test_straggler_evicted_at_deadline(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULT_STRAGGLE_S", "2.0")
    base, _ = _baseline()
    co, ws = _fleet(tmp_path, n=2, step_deadline_s=0.3)
    co.run(2)
    faultinject.arm("trainer_straggle", at=0)
    t0 = time.monotonic()
    co.run(8)
    wall = time.monotonic() - t0
    assert co.commits() == base
    assert co.evictions_total == 1
    assert wall < 2.0, (
        f"coordinator waited {wall:.1f}s — the straggler deadline "
        "did not cut the stall short")
    _teardown(co, ws)


def test_partition_typed_evict_then_rejoin(tmp_path):
    base, _ = _baseline()
    co, ws = _fleet(tmp_path, n=2)
    co.run(2)
    faultinject.arm("train_net_partition", at=0, times=2)
    co.run(8)
    assert co.commits() == base
    assert co.evictions_total >= 1
    assert co.rejoins_total >= 1, "healed partition never rejoined"
    assert co.last_recover_s is not None and co.last_recover_s >= 0
    reasons = [e["reason"] for e in co.events()
               if e["kind"] == "evicted"]
    assert any("RemoteUnavailableError" in r for r in reasons), reasons
    _teardown(co, ws)


def test_all_workers_gone_is_typed_unavailable(tmp_path):
    co, ws = _fleet(tmp_path, n=1, admit_deadline_s=0.3,
                    step_deadline_s=0.5)
    co.run(1)
    ws[0].close()
    with pytest.raises(NoTrainWorkersError) as ei:
        co.run(3)
    assert isinstance(ei.value, ServiceUnavailableError)
    _teardown(co, ws)


def test_late_replacement_worker_catches_up(tmp_path):
    """Elastic up: a worker admitted mid-run receives the task and the
    last committed state, then serves shards for subsequent steps."""
    base, _ = _baseline()
    co, ws = _fleet(tmp_path, n=1)
    co.run(6)
    w2 = TrainWorkerServer()
    co.admit(w2.addr)
    co.run(4)
    assert co.commits() == base
    assert w2.last_step == 10
    assert w2.committed_step == 10      # verified the commit barrier
    _teardown(co, ws + [w2])


# ---------------------------------------------------------------------------
# commit barrier
# ---------------------------------------------------------------------------


def test_followers_verify_and_refuse_wrong_sha(tmp_path):
    w = TrainWorkerServer()
    client = WorkerClient(w.addr)
    state = {"w": np.arange(4, dtype=np.float32)}
    good = client.commit(3, state, state_sha(state))
    assert good["ok"] is True
    assert w.committed_step == 3
    bad = client.commit(4, state, "0" * 64)
    assert bad["ok"] is False
    assert bad["sha"] == state_sha(state)   # reports what IT computed
    assert w.committed_step == 3            # refused commit not taken
    assert w.stats()["commit_mismatches_total"] == 1
    client.close()
    w.close()


def test_coordinator_crash_parks_workers_resume_sha_parity(tmp_path):
    base, _ = _baseline()
    co, ws = _fleet(tmp_path, n=2)
    co.run(5)
    faultinject.arm("coordinator_crash", at=1)
    with pytest.raises(SimulatedCrash):
        co.run(5)
    faultinject.disarm()
    assert co.step == 6                 # one step ran, then the crash
    co.close()                          # the process is "gone"
    # workers are parked: alive, counting coordinator silence
    for w in ws:
        assert w.coordinator_age_s() >= 0
    co2 = TrainCoordinator(LinRegTask(seed=5),
                           [w.addr for w in ws],
                           str(tmp_path / "ckpts"),
                           commit_interval=5, n_shards=4)
    assert co2.step == 5                # resumed at last COMMITTED
    co2.run(5)
    assert co2.commits()[-1] == base[-1]
    _teardown(co2, ws)


def test_resume_discards_uncommitted_tail_bit_deterministically(
        tmp_path):
    """Kill between commits: steps past the last barrier are recomputed
    on resume and land on the SAME bits (the headline guarantee)."""
    base, _ = _baseline(steps=20)
    co, ws = _fleet(tmp_path, n=2)
    co.run(13)                          # 3 steps past the serial-10
    co.close()                          # barrier die uncommitted
    co2, _ = _fleet(tmp_path, n=0)
    co2._clients = []                   # reuse dir; fresh workers below
    for w in ws:
        co2.admit(w.addr)
    assert co2.step == 10
    co2.run(10)
    # co2's first recorded commit is the resumed serial-10 one
    assert co2.commits() == base[1:], (co2.commits(), base)
    _teardown(co2, ws)


# ---------------------------------------------------------------------------
# ops plane
# ---------------------------------------------------------------------------


def test_stats_worker_rows_and_namespaced_metrics(tmp_path):
    co, ws = _fleet(tmp_path, n=2)
    co.run(6)
    co.membership.refresh_once()        # one heartbeat sweep caches
    snap = co.stats()                   # each worker's remote stats
    assert snap["step"] == 6
    assert snap["committed_step"] == 5
    assert snap["world_size"] == 2
    assert len(snap["workers"]) == 2
    for row in snap["workers"]:
        assert row["admitted"] is True
        assert row["last_step"] == 6
        assert row["step_time_p50_ms"] is not None
        assert row["heartbeat_age_s"] is not None
        assert row["evictions"] == 0 and row["rejoins"] == 0
        # the remote worker's own stats ride along (heartbeat payload)
        assert row["remote"].get("steps_total", 0) > 0
    # merged metrics: per-worker namespaces, no collisions
    names = [row["name"] for row in snap["workers"]]
    for name in names:
        assert snap["metrics"][f"{name}/train_steps_total"] > 0
        assert f"{name}/step_time_s" in snap["metrics"]
    assert snap["membership"]["members"] == 2
    _teardown(co, ws)


def test_membership_heartbeat_counts_eviction_and_rejoin(tmp_path):
    co, ws = _fleet(tmp_path, n=2)
    co.run(2)
    assert co.membership.refresh_once() == 2
    faultinject.arm("train_net_partition", at=0, times=2)
    assert co.membership.refresh_once() < 2     # partitioned member
    assert co.membership.refresh_once() == 2    # healed
    assert co.membership.stats()["rejoins_total"] >= 1
    _teardown(co, ws)


def test_worker_server_stats_surface(tmp_path):
    w = TrainWorkerServer()
    client = WorkerClient(w.addr)
    client.configure(LinRegTask(seed=1).spec())
    reply = client.rpc({"type": "stats"})
    snap = reply["value"]
    assert snap["task"]["kind"] == "linreg"
    assert snap["total_compiles"] == 0
    assert snap["coordinator_age_s"] >= 0
    # an unknown verb comes back as a typed wire error, not a hang
    from paddle_tpu.serving.batching import ServingError
    with pytest.raises(ServingError, match="unknown verb"):
        client.rpc({"type": "warp"})
    client.close()
    w.close()


# ---------------------------------------------------------------------------
# compiled tier: elastic-up gate
# ---------------------------------------------------------------------------


def test_program_task_replacement_joins_mid_run(tmp_path):
    """The elastic-up gate for the compiled tier: a replacement worker
    admitted mid-run rebuilds the program from the wire spec, serves
    real program gradients, and leaves the coordinator's losses and
    commits where an uninterrupted single-worker run puts them."""
    wr = TrainWorkerServer()
    ref = TrainCoordinator(ProgramGradTask(seed=1), [wr.addr],
                           str(tmp_path / "ref"),
                           commit_interval=3, n_shards=2)
    ref.run(6)
    ref_losses, ref_commits = ref.losses(), ref.commits()
    _teardown(ref, [wr])

    wa = TrainWorkerServer()
    co = TrainCoordinator(ProgramGradTask(seed=1), [wa.addr],
                          str(tmp_path / "ckpts"),
                          commit_interval=3, n_shards=2)
    co.run(3)
    wc = TrainWorkerServer()
    co.admit(wc.addr)
    co.run(3)
    assert wc.last_step == 6
    assert wc.total_compiles() >= 1     # it ran the program itself
    assert co.losses() == ref_losses
    assert co.commits() == ref_commits
    _teardown(co, [wa, wc])
