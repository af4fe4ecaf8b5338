#!/usr/bin/env python
"""trainbench — elastic training fabric bench + multi-process chaos
drill (cluster/train_fabric.py, cluster/train_worker.py).

Default mode is a loopback throughput bench: an in-process fleet runs
N coordinated steps and reports steps/s and per-worker step-time
percentiles.

``--chaos`` is the headline drill behind selfcheck stage 12: REAL
subprocess workers (``python -m paddle_tpu.cluster.train_worker``),
all four trainer fault points fired against one run —

1. ``trainer_crash_at_step`` (env-armed, ``--hard-exit``: the worker
   takes an ``os._exit`` mid-step — the SIGKILL shape), the
   coordinator evicts and retries at reduced world size, and a
   REPLACEMENT worker is folded back in (elastic up,
   ``train_elastic_resume_s``);
2. ``trainer_straggle`` (env-armed stall past the coordinator's
   straggler deadline): evicted typed, REJOINS after the stall heals
   (``train_recover_s``);
3. ``train_net_partition`` (armed coordinator-side): the RPC route
   vanishes typed for two calls, the worker is evicted and rejoins
   when the route heals;
4. ``coordinator_crash`` (SimulatedCrash — no exit checkpoint): the
   workers park, a NEW coordinator resumes from the last committed
   serial.

PASS requires the chaos run's committed ``(serial, sha)`` sequence to
EQUAL an uninterrupted single-worker reference run's — zero lost
committed steps AND bit-deterministic resume — plus loss-curve parity.
``--no-recover`` disables elasticity (the teeth-check: the drill must
then FAIL, proving the assertions detect lost runs).

Usage:
    python tools/trainbench.py [--steps 60] [--workers 2]
    python tools/trainbench.py --chaos [--task linreg|program]
                               [--steps 20] [--no-recover]
                               [--json] [--out FILE]
Pure CPU; exit 0 on pass, 1 on failure.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _task(kind, seed=11):
    from paddle_tpu.cluster.train_fabric import (LinRegTask,
                                                 ProgramGradTask)
    if kind == "linreg":
        return LinRegTask(seed=seed)
    return ProgramGradTask(seed=seed)


def _reference_run(kind, steps, commit_interval, n_shards):
    """Uninterrupted single-worker run: the parity target."""
    from paddle_tpu.cluster.train_fabric import TrainCoordinator
    from paddle_tpu.cluster.train_worker import TrainWorkerServer
    d = tempfile.mkdtemp(prefix="trainbench_ref_")
    w = TrainWorkerServer()
    co = TrainCoordinator(_task(kind), [w.addr], d,
                          commit_interval=commit_interval,
                          n_shards=n_shards)
    co.run(steps)
    commits, losses = co.commits(), co.losses()
    co.close()
    w.close()
    return commits, losses


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_worker(port, faults=None, straggle_s=None, hard_exit=False):
    """Launch a real subprocess worker; block until its ready line."""
    cmd = [sys.executable, "-m", "paddle_tpu.cluster.train_worker",
           "--host", "127.0.0.1", "--port", str(port)]
    if hard_exit:
        cmd += ["--hard-exit"]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if faults:
        env["PADDLE_TPU_FAULTS"] = faults
    if straggle_s is not None:
        env["PADDLE_TPU_FAULT_STRAGGLE_S"] = str(straggle_s)
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + 120.0
    for line in proc.stdout:
        if "ready on" in line:
            return proc
        if time.monotonic() > deadline:
            break
    proc.kill()
    raise RuntimeError(f"worker on port {port} never became ready")


def bench_main(args):
    from paddle_tpu.cluster.train_fabric import TrainCoordinator
    from paddle_tpu.cluster.train_worker import TrainWorkerServer
    workers = [TrainWorkerServer() for _ in range(args.workers)]
    co = TrainCoordinator(
        _task(args.task), [w.addr for w in workers],
        tempfile.mkdtemp(prefix="trainbench_"),
        commit_interval=args.commit_interval,
        n_shards=max(args.workers * 2, 4))
    t0 = time.monotonic()
    co.run(args.steps)
    wall = time.monotonic() - t0
    snap = co.stats()
    steps_s = args.steps / wall
    report = {
        "mode": "bench", "task": args.task, "steps": args.steps,
        "workers": args.workers, "wall_s": round(wall, 3),
        "steps_per_s": round(steps_s, 2),
        "worker_rows": [
            {k: r[k] for k in ("name", "last_step",
                               "step_time_p50_ms",
                               "step_time_p99_ms")}
            for r in snap["workers"]],
        "bench_record": {
            "metric": "train_fabric_steps_per_s",
            "value": round(steps_s, 2), "unit": "steps/s",
            "backend": "cpu", "workers": args.workers,
            "task": args.task},
    }
    co.close()
    for w in workers:
        w.close()
    _emit(args, report,
          f"trainbench: {args.steps} steps x {args.workers} workers "
          f"in {wall:.2f}s ({steps_s:.1f} steps/s)")
    return 0


def chaos_main(args):
    from paddle_tpu.cluster.train_fabric import TrainCoordinator
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.resilience.faultinject import SimulatedCrash

    kind = args.task
    steps = max(args.steps, 30)     # the 4 phases need the room
    commit_interval, n_shards = 5, 4
    failures = []
    records = {}

    print(f"trainbench --chaos: reference run ({kind}, {steps} "
          "steps)...", flush=True)
    ref_commits, ref_losses = _reference_run(kind, steps,
                                             commit_interval, n_shards)

    ckpt_dir = tempfile.mkdtemp(prefix="trainbench_chaos_")
    # w1 dies hard on its 3rd served step; w2 straggles once later
    w1 = _spawn_worker(_free_port(),
                       faults="trainer_crash_at_step@2",
                       hard_exit=True)
    # w2's 11th handled step stalls: steps 1-6 plus the crash retry
    # are 7 handles in phase 1, 2 more after w3 joins — index 10
    # lands inside phase 2's window, after the warmup deadline drops
    w2 = _spawn_worker(_free_port(),
                       faults="trainer_straggle@10", straggle_s=3.0)
    w1_addr = None
    w2_addr = None
    procs = [w1, w2]
    try:
        # recover the addresses from the spawn ports: the ready lines
        # were consumed by _spawn_worker, so re-derive from the cmd
        w1_addr = f"127.0.0.1:{w1.args[w1.args.index('--port') + 1]}"
        w2_addr = f"127.0.0.1:{w2.args[w2.args.index('--port') + 1]}"
        co = TrainCoordinator(
            _task(kind), [w1_addr, w2_addr], ckpt_dir,
            commit_interval=commit_interval, n_shards=n_shards,
            step_deadline_s=30.0, admit_deadline_s=10.0,
            readmit_interval_s=0.1, elastic=not args.no_recover)

        # --- phase 1: hard worker crash + elastic replacement -------
        print("phase 1: trainer_crash_at_step (hard exit) ...",
              flush=True)
        co.run(6)
        if co.evictions_total < 1:
            failures.append("w1's hard crash never evicted it")
        w3_port = _free_port()
        t0 = time.monotonic()
        w3 = _spawn_worker(w3_port)
        procs.append(w3)
        w3_addr = f"127.0.0.1:{w3_port}"
        w3_client = co.admit(w3_addr)
        co.run(2)                       # the admit sweep folds w3 in
        if not w3_client.admitted:
            failures.append("replacement worker w3 was never admitted")
        records["train_elastic_resume_s"] = round(
            time.monotonic() - t0, 3)

        # --- phase 2: straggler evict + rejoin ----------------------
        print("phase 2: trainer_straggle past the deadline ...",
              flush=True)
        # every program is warm now: a 3s stall against a 1.5s
        # deadline is an unambiguous straggler
        co.step_deadline_s = 1.5
        evict_before = co.evictions_total
        rejoin_before = co.rejoins_total
        co.run(4)                       # w2's 11th handle stalls 3s
        deadline = time.monotonic() + 15.0
        while (co.rejoins_total <= rejoin_before
               and time.monotonic() < deadline
               and co.step < steps - 4):
            # pace the loop so the readmit backoff can elapse — the
            # reduced fleet steps in microseconds otherwise
            time.sleep(0.15)
            co.run(1)
        if co.evictions_total <= evict_before:
            failures.append("the straggler was never evicted")
        if co.rejoins_total <= rejoin_before:
            failures.append("the healed straggler never rejoined")
        records["train_recover_s"] = co.last_recover_s and round(
            co.last_recover_s, 3)

        # --- phase 3: net partition (coordinator side) --------------
        print("phase 3: train_net_partition x2 ...", flush=True)
        faultinject.arm("train_net_partition", at=0, times=2)
        co.run(2)
        faultinject.disarm("train_net_partition")

        # --- phase 4: coordinator crash + resume --------------------
        print("phase 4: coordinator_crash + resume ...", flush=True)
        faultinject.arm("coordinator_crash", at=0)
        crashed = False
        try:
            co.run(max(1, steps - co.step))
        except SimulatedCrash:
            crashed = True
        faultinject.disarm()
        if not crashed:
            failures.append("coordinator_crash never fired")
        co_totals = (co.evictions_total, co.rejoins_total,
                     co.retries_total)
        co.close()
        co2 = TrainCoordinator(
            _task(kind),
            [w2_addr, f"127.0.0.1:{w3_port}"], ckpt_dir,
            commit_interval=commit_interval, n_shards=n_shards,
            step_deadline_s=30.0, admit_deadline_s=10.0,
            readmit_interval_s=0.1, elastic=not args.no_recover)
        resumed_at = co2.step
        co2.run(steps - co2.step)
        chaos_commits, chaos_losses = co2.commits(), co2.losses()

        # --- verdicts ----------------------------------------------
        # zero lost committed steps + bit-deterministic resume
        ref_tail = [c for c in ref_commits if c[0] >= resumed_at]
        if chaos_commits != ref_tail and chaos_commits != ref_commits:
            failures.append(
                f"committed (serial, sha) diverged: chaos "
                f"{chaos_commits} vs reference {ref_commits}")
        # loss-curve parity for every step the resumed run computed
        ref_by_step = {i + 1: v for i, v in enumerate(ref_losses)}
        for i, loss in enumerate(chaos_losses):
            step = resumed_at + i + 1
            ref = ref_by_step.get(step)
            if ref is not None and abs(loss - ref) > 1e-6 * max(
                    1.0, abs(ref)):
                failures.append(
                    f"loss curve diverged at step {step}: "
                    f"{loss} vs {ref}")
                break
        snap = co2.stats()
        co2.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    report = {
        "mode": "chaos", "task": kind, "steps": steps,
        "resumed_at_serial": resumed_at,
        "reference_commits": [[s, sha] for s, sha in ref_commits],
        "chaos_commits": [[s, sha] for s, sha in chaos_commits],
        "evictions_total": co_totals[0] + snap["evictions_total"],
        "rejoins_total": co_totals[1] + snap["rejoins_total"],
        "retries_total": co_totals[2] + snap["retries_total"],
        "events": snap["events"],
        "failures": failures,
        "bench_record": {
            "metric": "train_recover_s",
            "value": records.get("train_recover_s"), "unit": "s",
            "backend": "cpu", "task": kind,
            "train_elastic_resume_s":
                records.get("train_elastic_resume_s")},
    }
    ok = not failures
    _emit(args, report,
          ("trainbench --chaos PASS: zero lost committed steps, "
           f"resume sha-deterministic at serial {resumed_at} "
           f"(recover {records.get('train_recover_s')}s, elastic "
           f"resume {records.get('train_elastic_resume_s')}s)")
          if ok else
          "trainbench --chaos FAIL:\n  - " + "\n  - ".join(failures))
    return 0 if ok else 1


def _emit(args, report, line):
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(line)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="training-fabric bench + multi-process chaos "
                    "drill")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--task", choices=("linreg", "program"),
                    default="linreg")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--commit-interval", type=int, default=5)
    ap.add_argument("--no-recover", action="store_true",
                    help="disable elastic eviction/retry — the drill "
                         "MUST fail (inverted teeth-check)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.steps is None:
        args.steps = 20 if args.chaos else 60
    # racecheck: ok(global-mutation) — single-process bench entrypoint:
    # runs before any thread or jax backend exists
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu as fluid
    # racecheck: ok(global-mutation) — ditto: entrypoint-owned process
    fluid.force_cpu()
    if args.chaos:
        try:
            return chaos_main(args)
        except Exception as exc:    # noqa: BLE001 — a typed failure
            # of the drill itself is a FAIL, not a crash dump
            print(f"trainbench --chaos FAIL: "
                  f"{type(exc).__name__}: {exc}")
            return 1
    return bench_main(args)


if __name__ == "__main__":
    sys.exit(main())
