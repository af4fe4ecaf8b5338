"""Serving subsystem tier-1 suite (paddle_tpu/serving/): micro-batch
coalescing correctness (bit-for-bit vs single-request runs), deadline
flush, bucket padding round-trips, queue-full shedding, per-request
timeouts, warmup compile-count assertions, and metrics snapshot
sanity. All CPU, deterministic: the queueing logic is pinned under an
injectable fake clock, and the engine tests drive real threads only
through states they must pass through (events, not sleeps, wherever
possible).
"""
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving import (BucketError, BucketSpec, MicroBatcher,
                                PendingResult, QueueFullError,
                                RequestTimeoutError, ServingConfig,
                                ServingEngine)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# buckets.py — pure policy/padding math
# ---------------------------------------------------------------------------

def test_bucket_selection_and_errors():
    spec = BucketSpec(batch_sizes=(1, 2, 4, 8),
                      seq_lens={"tok": (8, 16)})
    assert spec.batch_bucket(1) == 1
    assert spec.batch_bucket(3) == 4
    assert spec.batch_bucket(8) == 8
    with pytest.raises(BucketError):
        spec.batch_bucket(9)
    assert spec.seq_bucket("tok", 5) == 8
    assert spec.seq_bucket("tok", 16) == 16
    with pytest.raises(BucketError):
        spec.seq_bucket("tok", 17)
    # non-bucketed inputs pass through
    assert spec.seq_bucket("img", 999) == 999
    with pytest.raises(ValueError):
        BucketSpec(batch_sizes=())
    with pytest.raises(ValueError):
        BucketSpec(batch_sizes=(0, 2))


def test_signature_groups_by_padded_length():
    spec = BucketSpec(batch_sizes=(1, 4), seq_lens={"tok": (8, 16)})
    f5 = {"tok": np.zeros((1, 5), np.int64)}
    f7 = {"tok": np.zeros((1, 7), np.int64)}
    f12 = {"tok": np.zeros((1, 12), np.int64)}
    # 5 and 7 pad to the same 8-bucket — same signature, coalescable
    assert spec.signature(f5) == spec.signature(f7) == (("tok", 8),)
    assert spec.signature(f12) == (("tok", 16),)
    # inputs without length buckets contribute nothing
    assert BucketSpec(batch_sizes=(1,)).signature(
        {"img": np.zeros((1, 3, 4, 4))}) == ()


def test_pad_batch_round_trip():
    spec = BucketSpec(batch_sizes=(1, 2, 4, 8),
                      seq_lens={"tok": (8,)}, pad_values={"tok": 7})
    feeds = [{"tok": np.arange(5, dtype=np.int64).reshape(1, 5)},
             {"tok": np.arange(6, dtype=np.int64).reshape(2, 3)}]
    batch, n_rows, bucket_rows = spec.pad_batch(feeds)
    assert n_rows == 3 and bucket_rows == 4
    assert batch["tok"].shape == (4, 8)
    # sequence positions pad with the declared pad value
    assert (batch["tok"][0, 5:] == 7).all()
    # pad ROWS replicate row 0 (real data, not zeros)
    np.testing.assert_array_equal(batch["tok"][3], batch["tok"][0])
    # unpad splits per-request rows back out and drops the pad row
    outs = BucketSpec.unpad_rows([batch["tok"]], [1, 2])
    assert outs[0][0].shape == (1, 8) and outs[1][0].shape == (2, 8)
    np.testing.assert_array_equal(outs[1][0], batch["tok"][1:3])
    # scalar fetches replicate to every request
    outs = BucketSpec.unpad_rows([np.float32(3.5)], [1, 2])
    assert outs[0][0] == outs[1][0] == np.float32(3.5)


def test_all_signatures_is_the_warmup_set():
    spec = BucketSpec(batch_sizes=(2, 4), seq_lens={"tok": (8, 16)})
    sigs = spec.all_signatures()
    assert len(sigs) == 4
    assert (2, (("tok", 8),)) in sigs and (4, (("tok", 16),)) in sigs
    # restricted to actually-fed names
    assert spec.all_signatures(names={"img"}) == [(2, ()), (4, ())]


# ---------------------------------------------------------------------------
# batching.py — deterministic queueing under a fake clock
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _req(n_rows=1, sig=(), deadline=None, at=None, clock=None):
    t = at if at is not None else (clock.t if clock else 0.0)
    return PendingResult(feed={}, n_rows=n_rows, signature=sig,
                         deadline=deadline, enqueued_at=t)


def test_batcher_flushes_full_batch_immediately():
    clk = FakeClock()
    mb = MicroBatcher(max_batch_size=4, max_wait_s=10.0, max_queue=16,
                      clock=clk)
    reqs = [_req(2, clock=clk), _req(2, clock=clk), _req(1, clock=clk)]
    for r in reqs:
        mb.put(r)
    batch, expired = mb.next_batch()
    assert batch == reqs[:2] and not expired   # 4 rows = full, no wait
    assert mb.depth() == 1


def test_batcher_deadline_flushes_partial_batch():
    clk = FakeClock()
    mb = MicroBatcher(max_batch_size=8, max_wait_s=0.5, max_queue=16,
                      clock=clk)
    r = _req(3, clock=clk)
    mb.put(r)
    clk.t += 0.6          # oldest member's window has expired
    batch, expired = mb.next_batch()
    assert batch == [r] and not expired


def test_batcher_groups_by_signature():
    clk = FakeClock()
    mb = MicroBatcher(max_batch_size=4, max_wait_s=0.0, max_queue=16,
                      clock=clk)
    a1, b1, a2 = (_req(2, sig="A", clock=clk),
                  _req(2, sig="B", clock=clk),
                  _req(2, sig="A", clock=clk))
    for r in (a1, b1, a2):
        mb.put(r)
    batch, _ = mb.next_batch()
    assert batch == [a1, a2]          # same-signature followers jump in
    batch, _ = mb.next_batch()
    assert batch == [b1]


def test_batcher_sweeps_expired_before_serving():
    clk = FakeClock()
    mb = MicroBatcher(max_batch_size=4, max_wait_s=0.0, max_queue=16,
                      clock=clk)
    dead = _req(1, deadline=clk.t - 1.0, clock=clk)
    live = _req(1, clock=clk)
    mb.put(dead)
    mb.put(live)
    batch, expired = mb.next_batch()
    assert expired == [dead] and batch == []   # sweep reports first
    batch, expired = mb.next_batch()
    assert batch == [live] and not expired


def test_batcher_sheds_at_capacity():
    mb = MicroBatcher(max_batch_size=4, max_wait_s=0.0, max_queue=2)
    mb.put(_req(1))
    mb.put(_req(1))
    with pytest.raises(QueueFullError):
        mb.put(_req(1))


# ---------------------------------------------------------------------------
# engine.py — end to end on a real program
# ---------------------------------------------------------------------------

def _make_model():
    """Tiny per-row model: fc-relu-fc-softmax on [rows, 8] — outputs
    are row-independent, so coalescing must be bit-exact per row."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
    infer = main.clone(for_test=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return infer, pred, scope


def _engine(infer, pred, scope, **kw):
    kw.setdefault("buckets", BucketSpec(batch_sizes=(1, 2, 4, 8)))
    kw.setdefault("config", ServingConfig(max_wait_ms=30.0,
                                          max_queue=32))
    return ServingEngine(infer, ["x"], [pred], scope=scope,
                         place=fluid.CPUPlace(), **kw)


def test_batched_results_bit_exact_vs_single_request():
    """The acceptance pin: concurrent coalesced requests return, row
    for row, what each request gets when served alone, to the rounding of
    a float32 sum taken in another order: the rows alone run the 1-, 2-
    and 4-bucket's executables and together the 8-bucket's, and the CPU's
    matrix product and the softmax's sum are free to split 8 and 16 terms
    differently at another row count (largest difference met 2.2e-08, 2.5e-07
    of the value). A row that read a NEIGHBOUR's values would differ in
    its first digits."""
    infer, pred, scope = _make_model()
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(n, 8).astype(np.float32)}
             for n in (1, 2, 1, 3)]           # 7 rows -> one 8-bucket
    with _engine(infer, pred, scope,
                 config=ServingConfig(max_wait_ms=200.0)) as eng:
        eng.warmup()
        # async submits land in one micro-batch window: 7 rows never
        # fill the 8-bucket, so the batcher MUST hold all four until
        # the deadline (wide enough to dwarf any CI scheduling stall)
        # — exactly one coalesced batch, deterministically
        pending = [eng.submit(f, timeout=30.0) for f in feeds]
        results = [p.result(timeout=30.0) for p in pending]
        stats = eng.stats()
        eng.assert_no_recompiles()

        # single-request reference through the same engine
        singles = [eng.infer(f, timeout=30.0) for f in feeds]

    for got, ref, feed in zip(results, singles, feeds):
        assert got[0].shape == (feed["x"].shape[0], 10)
        np.testing.assert_allclose(got[0], ref[0], rtol=2e-6, atol=1e-7)
    assert stats["responses_total"] == len(feeds)
    assert stats["batches_total"] == 1        # all four coalesced
    assert stats["rows_total"] == 7 and stats["padded_rows_total"] == 8


def test_deadline_flush_serves_partial_batch():
    """A lone request must not wait for a full bucket: the max_wait
    deadline flushes a partial batch."""
    infer, pred, scope = _make_model()
    with _engine(infer, pred, scope,
                 config=ServingConfig(max_wait_ms=5.0)) as eng:
        eng.warmup()
        t0 = time.monotonic()
        out = eng.infer({"x": np.zeros((3, 8), np.float32)},
                        timeout=30.0)
        elapsed = time.monotonic() - t0
        stats = eng.stats()
    assert out[0].shape == (3, 10)
    # padded 3 -> 4 bucket; fill ratio reflects the pad row
    assert stats["rows_total"] == 3 and stats["padded_rows_total"] == 4
    assert elapsed < 10.0, "deadline flush never happened"


def test_queue_full_sheds_with_metrics():
    infer, pred, scope = _make_model()
    eng = _engine(infer, pred, scope, auto_start=False,
                  config=ServingConfig(max_wait_ms=1.0, max_queue=2))
    try:
        feed = {"x": np.zeros((1, 8), np.float32)}
        eng.submit(feed)
        eng.submit(feed)
        with pytest.raises(QueueFullError):
            eng.submit(feed)
        # an oversize request sheds too, with a structured BucketError
        with pytest.raises(BucketError):
            eng.submit({"x": np.zeros((9, 8), np.float32)})
        stats = eng.stats()
        assert stats["shed_total"] == 2
        assert stats["requests_total"] == 2      # rejected != admitted
        assert stats["queue_depth"] == 2
    finally:
        eng.close()


def test_per_request_timeout_structured_error():
    infer, pred, scope = _make_model()
    eng = _engine(infer, pred, scope, auto_start=False)
    try:
        req = eng.submit({"x": np.zeros((1, 8), np.float32)},
                         timeout=0.01)
        time.sleep(0.05)          # deadline blows while worker is down
        eng.start()
        with pytest.raises(RequestTimeoutError):
            req.result(timeout=10.0)
        deadline = time.monotonic() + 5.0
        while eng.stats()["timeouts_total"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.stats()["timeouts_total"] == 1
    finally:
        eng.close()


def test_warmup_compiles_each_bucket_exactly_once():
    """(b) of the acceptance criteria: warmup compiles one executable
    per declared bucket, and steady-state traffic of every in-bucket
    size causes ZERO further compiles."""
    infer, pred, scope = _make_model()
    buckets = BucketSpec(batch_sizes=(1, 2, 4))
    with _engine(infer, pred, scope, buckets=buckets) as eng:
        report = eng.warmup()
        assert report == {"signatures": 3, "compiles": 3}
        assert eng.exe.total_compiles() == 3
        # one lowered program, three shape specializations
        keys = eng.exe.compile_cache_keys()
        assert len(keys) == 1
        assert eng.exe.compile_counts()[keys[0]] == 3
        rng = np.random.RandomState(1)
        for n in (1, 2, 3, 4, 1, 3, 2, 4):
            out = eng.infer({"x": rng.randn(n, 8).astype(np.float32)},
                            timeout=30.0)
            assert out[0].shape == (n, 10)
        eng.assert_no_recompiles()
        assert eng.exe.total_compiles() == 3


def test_seq_bucket_padding_end_to_end():
    """Length-bucketed token input: requests of different raw lengths
    run through pre-compiled (batch, len) buckets and only
    same-signature requests coalesce."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1, -1],
                                dtype="int64", append_batch_size=False)
        emb = fluid.layers.embedding(tok, size=[16, 8])
        pooled = fluid.layers.reduce_mean(emb, dim=1)
        pred = fluid.layers.fc(pooled, size=4, act="softmax")
    infer = main.clone(for_test=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    buckets = BucketSpec(batch_sizes=(1, 2), seq_lens={"tok": (4, 8)})
    with ServingEngine(infer, ["tok"], [pred], scope=scope,
                       place=fluid.CPUPlace(), buckets=buckets,
                       config=ServingConfig(max_wait_ms=5.0)) as eng:
        report = eng.warmup()
        assert report["signatures"] == 4      # 2 batch x 2 len buckets
        rng = np.random.RandomState(2)
        for length in (3, 4, 6, 8):
            out = eng.infer(
                {"tok": rng.randint(0, 16, (1, length)).astype(np.int64)},
                timeout=30.0)
            assert out[0].shape == (1, 4)
        eng.assert_no_recompiles()
        with pytest.raises(BucketError):
            eng.submit({"tok": np.zeros((1, 9), np.int64)})


def test_metrics_snapshot_sanity():
    infer, pred, scope = _make_model()
    with _engine(infer, pred, scope) as eng:
        eng.warmup()
        for n in (1, 2, 4):
            eng.infer({"x": np.zeros((n, 8), np.float32)}, timeout=30.0)
        stats = eng.stats()
    assert stats["requests_total"] == stats["responses_total"] == 3
    assert stats["errors_total"] == stats["shed_total"] == 0
    assert stats["timeouts_total"] == 0
    assert stats["batches_total"] >= 1
    assert stats["rows_total"] == 7
    assert stats["padded_rows_total"] >= stats["rows_total"]
    assert 0 < stats["batch_fill_ratio"] <= 1.0
    lat = stats["request_latency"]
    assert lat["p50_ms"] is not None
    assert lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
    assert stats["compiles_now"] == stats["warmup_compiles"] == 4
    # the snapshot is json-serializable (servebench prints it)
    import json
    json.dumps(stats)


def test_worker_retries_transient_device_errors():
    """The resilience reuse: an injected transient device error on the
    batch dispatch is retried AT THE SERVING LAYER (the engine's inner
    executor runs retry-free so attempts never multiply), counted in
    retries_total, and the request still succeeds."""
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.resilience.retry import RetryPolicy

    infer, pred, scope = _make_model()
    sleeps = []
    policy = RetryPolicy(max_attempts=3, initial_backoff=0.01,
                         sleep=sleeps.append)
    with _engine(infer, pred, scope,
                 config=ServingConfig(max_wait_ms=1.0,
                                      retry_policy=policy)) as eng:
        eng.warmup()
        faultinject.arm("device_error", at=0, times=1)
        try:
            out = eng.infer({"x": np.ones((1, 8), np.float32)},
                            timeout=30.0)
        finally:
            faultinject.disarm()
        stats = eng.stats()
    assert out[0].shape == (1, 10)
    assert stats["retries_total"] == 1
    assert stats["errors_total"] == 0
    assert stats["responses_total"] == 1
    assert sleeps == [0.01]          # the policy's schedule was used


def test_worker_survives_request_errors():
    """A bad batch fails its requests with the real exception but the
    worker keeps serving later traffic."""
    infer, pred, scope = _make_model()
    with _engine(infer, pred, scope) as eng:
        eng.warmup()
        with pytest.raises(Exception):
            # wrong trailing dim -> lowering/shape failure inside run
            eng.infer({"x": np.zeros((1, 5), np.float32)},
                      timeout=30.0)
        out = eng.infer({"x": np.zeros((1, 8), np.float32)},
                        timeout=30.0)
        stats = eng.stats()
    assert out[0].shape == (1, 10)
    assert stats["errors_total"] == 1
    assert stats["responses_total"] == 1


def test_serving_from_saved_model_and_inferencer(tmp_path):
    """The deployment loop: save_inference_model -> ServingEngine
    .from_saved_model serves identical results to direct infer; the
    Inferencer.from_inference_model/serve() wrapper agrees too."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        pred = fluid.layers.fc(x, size=10, act="softmax")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
        ref = np.asarray(exe.run(main.clone(for_test=True),
                                 feed={"x": np.ones((2, 8), np.float32)},
                                 fetch_list=[pred], mode="test")[0])

    with ServingEngine.from_saved_model(
            d, place=fluid.CPUPlace(),
            buckets=BucketSpec(batch_sizes=(1, 2)),
            config=ServingConfig(max_wait_ms=5.0)) as eng:
        eng.warmup()
        out = eng.infer({"x": np.ones((2, 8), np.float32)},
                        timeout=30.0)
    np.testing.assert_allclose(out[0], ref, rtol=1e-6)

    inf = fluid.Inferencer.from_inference_model(d,
                                                place=fluid.CPUPlace())
    assert inf.feed_names == ["x"]
    direct = np.asarray(inf.infer(
        {"x": np.ones((2, 8), np.float32)})[0])
    np.testing.assert_allclose(direct, ref, rtol=1e-6)
    with inf.serve(buckets=BucketSpec(batch_sizes=(1, 2)),
                   config=ServingConfig(max_wait_ms=5.0)) as eng2:
        eng2.warmup()
        served = eng2.infer({"x": np.ones((2, 8), np.float32)},
                            timeout=30.0)
    np.testing.assert_array_equal(served[0], direct)
