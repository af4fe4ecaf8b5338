"""Continuous-batching decode serving: paged KV cache + iteration-level
scheduler (serving/decode_engine.py, serving/kv_pages.py, and the
llama_paged_prefill / llama_paged_decode / llama_paged_spec_step ops
they dispatch).

The two contracts everything else hangs off:

* **numerics never depend on batch composition** — a request's greedy
  tokens are BIT-identical whether it runs alone or co-scheduled with
  any mix of neighbours (each row's math touches only its own row and
  its own pages), and identical to the fused ``build_llama_generator``
  program serving the same scope;
* **zero recompiles under churn** — the decode-step executable
  compiles once per (model config, max_batch); requests of varied
  lengths joining and leaving mid-stream never change a traced shape
  (``Executor.compile_counts`` pinned across a 3x-max_batch churn).
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import (LlamaConfig, build_llama_generator,
                                     copy_weights_as_draft,
                                     quantize_generator_weights)
from paddle_tpu.resilience import faultinject
from paddle_tpu.resilience.retry import RetryPolicy, TransientDeviceError
from paddle_tpu.serving.decode_engine import projected_peak
from paddle_tpu.serving import (BucketError, DecodeConfig, DecodeEngine,
                                PageAllocator, PagesExhaustedError,
                                PoolsLostError, QueueFullError,
                                RequestTimeoutError, WorkerDiedError)

from pool_donation import aliased_bytes, check_dispatch_donates

pytestmark = pytest.mark.serving

CFG = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=64, dtype="float32")
GEN_PROMPT, GEN_NEW = 6, 8


@pytest.fixture(scope="module")
def served_scope():
    """Scope holding generator-layout weights (+ the fused reference
    program) shared by every engine in this module."""
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[1, GEN_PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(CFG, ptok,
                                        max_new_tokens=GEN_NEW)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return scope, exe, gen_p, gen_out


@pytest.fixture(scope="module")
def engine(served_scope):
    scope = served_scope[0]
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=4, prompt_buckets=(4, 8),
                            max_new_tokens=8, page_size=8,
                            decode_block=4, prefill_batch=2,
                            default_timeout_s=120.0))
    eng.warmup()
    yield eng
    eng.close()


def _prompts(n, rng, lo=2, hi=8):
    return [rng.randint(0, CFG.vocab_size,
                        (int(rng.randint(lo, hi + 1)),)).astype(np.int64)
            for _ in range(n)]


# ---------------------------------------------------------------------
# page allocator (pure host-side unit tests)
# ---------------------------------------------------------------------

def test_page_allocator_basics():
    al = PageAllocator(n_pages=5, page_size=4)
    assert al.usable_pages == 4          # page 0 reserved
    assert al.pages_for(1) == 1 and al.pages_for(4) == 1
    assert al.pages_for(5) == 2
    got = al.alloc(3)
    assert got == [1, 2, 3] and al.available == 1 and al.in_use == 3
    with pytest.raises(PagesExhaustedError):
        al.alloc(2)
    assert al.available == 1             # failed alloc grants nothing
    al.free([2])
    assert sorted(al.alloc(2)) == [2, 4]


def test_page_allocator_exhaustion_is_queue_full_semantics():
    al = PageAllocator(n_pages=3, page_size=4)
    al.alloc(2)
    with pytest.raises(QueueFullError):   # typed shed, client backs off
        al.alloc(1)


def test_page_allocator_invariants():
    al = PageAllocator(n_pages=4, page_size=2)
    pages = al.alloc(2)
    al.free(pages[:1])
    with pytest.raises(ValueError):       # double free
        al.free(pages[:1])
    with pytest.raises(ValueError):       # null page never returnable
        al.free([0])
    with pytest.raises(ValueError):
        PageAllocator(n_pages=1, page_size=4)


# ---------------------------------------------------------------------
# ServingMetrics percentile windows (pure host-side unit tests)
# ---------------------------------------------------------------------

def test_metrics_stats_safe_on_empty_window():
    """stats() must be callable before any request completes (servebench
    polls it mid-warmup): empty windows report None percentiles and
    count 0, never IndexError/NaN."""
    from paddle_tpu.serving import ServingMetrics
    m = ServingMetrics()
    snap = m.stats()
    for window in ("request_latency", "batch_latency"):
        assert snap[window] == {"p50_ms": None, "p95_ms": None,
                                "p99_ms": None, "count": 0}


def test_metrics_stats_one_sample_window():
    """A one-sample window reports that sample at every percentile."""
    from paddle_tpu.serving import ServingMetrics
    m = ServingMetrics()
    m.observe_latency(0.25)
    m.observe_window("ttft_s", 0.5)
    snap = m.stats()
    lat = snap["request_latency"]
    assert lat["count"] == 1
    assert lat["p50_ms"] == lat["p95_ms"] == lat["p99_ms"] == 250.0
    assert snap["ttft_s"] == {"p50_ms": 500.0, "p95_ms": 500.0,
                              "p99_ms": 500.0, "count": 1}


def test_metrics_nonfinite_samples_never_poison_percentiles():
    """NaN/inf samples are dropped at the door (observe_window) or
    filtered in the snapshot — one bad sample must not turn every
    percentile into NaN."""
    from paddle_tpu.serving import ServingMetrics
    m = ServingMetrics()
    m.observe_window("ttft_s", float("nan"))
    m.observe_window("ttft_s", float("inf"))
    assert "ttft_s" not in m.stats()     # nothing admitted, no window
    m.observe_window("ttft_s", 0.1)
    snap = m.stats()["ttft_s"]
    assert snap["count"] == 1 and snap["p99_ms"] == 100.0


def test_metrics_counter_deltas_include_extra_counters():
    """counter_deltas() spans the extended decode vocabulary, not just
    the base _COUNTERS set."""
    from paddle_tpu.serving import ServingMetrics
    m = ServingMetrics(extra_counters=("generated_tokens_total",))
    before = m.stats()
    m.incr("generated_tokens_total", 7)
    assert m.counter_deltas(before)["generated_tokens_total"] == 7


# ---------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------

def test_engine_matches_fused_generator(served_scope, engine):
    """The paged step programs serve the exact greedy tokens the fused
    llama_generate program produces from the same scope."""
    scope, exe, gen_p, gen_out = served_scope
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, CFG.vocab_size, (1, GEN_PROMPT)).astype(
        np.int64)
    with fluid.scope_guard(scope):
        ref = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])
    got = engine.generate(prompt[0], max_new=GEN_NEW, timeout=120)
    np.testing.assert_array_equal(got, ref[0, GEN_PROMPT:])


def test_churn_no_recompiles_and_bit_identical(engine):
    """3x max_batch requests of varied lengths and varied max_new join
    and leave mid-stream; zero XLA compiles, and every request's tokens
    equal its run-alone tokens bit for bit."""
    rng = np.random.RandomState(1)
    prompts = _prompts(3 * engine.config.max_batch, rng)
    new_lens = [int(rng.randint(2, 9)) for _ in prompts]
    counts_before = engine.exe.compile_counts()
    reqs = [engine.submit(p, max_new=n, timeout=120)
            for p, n in zip(prompts, new_lens)]
    together = [r.result(120) for r in reqs]
    alone = [engine.generate(p, max_new=n, timeout=120)
             for p, n in zip(prompts, new_lens)]
    assert engine.exe.compile_counts() == counts_before
    engine.assert_no_recompiles()
    for a, b, n in zip(together, alone, new_lens):
        assert len(a) == n
        np.testing.assert_array_equal(a, b)
    st = engine.stats()
    assert st["responses_total"] >= 2 * len(prompts)
    assert st["ttft_s"]["count"] >= 2 * len(prompts)
    assert st["pages_in_use"] == 0       # everything retired and freed


# ---------------------------------------------------------------------
# single-row prefill programs: a group leaves the queue together and is
# dispatched request by request
# ---------------------------------------------------------------------

def _group_engine(scope, **over):
    """A cold engine with no worker, so that whatever is queued before
    ``start()`` is taken by ONE admission pass (up to prefill_batch)."""
    conf = dict(max_batch=4, prompt_buckets=(4, 8), max_new_tokens=8,
                page_size=8, decode_block=4, prefill_batch=4,
                default_timeout_s=120.0)
    draft_cfg = over.pop("draft_cfg", None)
    conf.update(over)
    return DecodeEngine(CFG, scope=scope, place=fluid.CPUPlace(),
                        draft_cfg=draft_cfg, config=DecodeConfig(**conf),
                        auto_start=False)


def _queue_together(eng, prompts, **kw):
    """Stop the worker, queue ``prompts``, start it again: the requests."""
    eng._stop.set()
    if eng._worker is not None:
        eng._worker.join(10.0)
    reqs = [eng.submit(p, timeout=120, **kw) for p in prompts]
    eng.start()
    return reqs


def _pool_bytes(pools):
    return [np.asarray(p).view(np.uint8) for p in pools]


@pytest.fixture(scope="module")
def group_engine(served_scope):
    eng = _group_engine(served_scope[0])
    eng.warm = eng.warmup()
    yield eng
    eng.close()


@pytest.mark.parametrize("bucket", [4, 8])
@pytest.mark.parametrize("size", [1, 2, 3, 4])
def test_groups_of_every_size_run_the_warmed_programs(group_engine, size,
                                                      bucket):
    """Two prefill programs and the decode program, whatever the group:
    1 to 4 requests of either bucket compile nothing, and each pays one
    dispatch of its bucket's length."""
    eng = group_engine
    assert eng.warm["programs"] == 3
    compiles = eng.exe.total_compiles()
    before = eng.stats()
    rng = np.random.RandomState(10 * bucket + size)
    prompts = _prompts(size, rng, lo=bucket // 2 + 1, hi=bucket)
    reqs = _queue_together(eng, prompts, max_new=3)
    assert all(r.result(120).size == 3 for r in reqs)
    eng.assert_no_recompiles()
    after = eng.stats()
    assert eng.exe.total_compiles() == compiles == after["compiles_now"]
    assert after["prefill_dispatch_total"] \
        - before["prefill_dispatch_total"] == size
    assert after["prefill_padded_tokens_total"] \
        - before["prefill_padded_tokens_total"] == size * bucket


def test_a_request_among_three_peers_is_the_request_alone(served_scope):
    """Queued third of four same-bucket requests, a prompt returns the
    tokens it returns alone and leaves, byte for byte, the pages it
    leaves alone: there is one executable a bucket, whatever shares the
    queue."""
    scope = served_scope[0]
    rng = np.random.RandomState(11)
    peers = _prompts(3, rng, lo=5, hi=8)
    probe = rng.randint(0, CFG.vocab_size, (7,)).astype(np.int64)
    mix = peers[:2] + [probe] + peers[2:]
    out = {}
    for name, prompts, at in (("alone", [probe], 0), ("mixed", mix, 2)):
        handoff = _group_engine(scope)
        tokens = _group_engine(scope)
        try:
            # prefill_only resolves with the page CONTENTS the prefill
            # left (fresh pools on both sides: unwritten entries are 0)
            blobs = [handoff.submit(p, max_new=4, prefill_only=True)
                     for p in prompts]
            reqs = [tokens.submit(p, max_new=6) for p in prompts]
            handoff.start(), tokens.start()
            out[name] = (blobs[at].result(120), reqs[at].result(120))
            assert tokens.stats()["prefill_dispatch_total"] \
                == len(prompts)
        finally:
            handoff.close(), tokens.close()
    (blob_a, tok_a), (blob_m, tok_m) = out["alone"], out["mixed"]
    np.testing.assert_array_equal(tok_a, tok_m)
    assert blob_a["emitted"] == blob_m["emitted"] == [int(tok_a[0])]
    assert len(blob_a["cache"]) == 2
    for a, m in zip(blob_a["cache"], blob_m["cache"]):
        assert np.asarray(a).any()
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(m).view(np.uint8))


@pytest.mark.parametrize("rows", [2, 4])
def test_run_prefill_program_takes_rows_one_dispatch_each(served_scope,
                                                          rows):
    """_run_prefill_program with k rows IS k calls of one row: the same
    next tokens, the same pools, and ``kept`` from the last row."""
    eng = _group_engine(served_scope[0])
    try:
        rng = np.random.RandomState(12)
        tokens = np.zeros((rows, 8), np.int64)
        lens = rng.randint(3, 9, (rows,)).astype(np.int32)
        table = np.zeros((rows, eng.pages_per_seq), np.int32)
        for i in range(rows):
            tokens[i, :lens[i]] = rng.randint(0, CFG.vocab_size, lens[i])
            table[i, :2] = (1 + 2 * i, 2 + 2 * i)
        runs = eng.exe.compile_counts()
        together = eng._run_prefill_program(8, tokens, lens, table)
        assert together.shape == (rows,)
        pools = _pool_bytes(eng._pools)
        eng._pools = [jnp.zeros_like(p) for p in eng._pools]
        one_by_one = [eng._run_prefill_program(
            8, tokens[i:i + 1], lens[i:i + 1], table[i:i + 1])
            for i in range(rows)]
        assert all(t.shape == (1,) for t in one_by_one)
        np.testing.assert_array_equal(together,
                                      np.concatenate(one_by_one))
        for a, b in zip(pools, _pool_bytes(eng._pools)):
            assert a.any()
            np.testing.assert_array_equal(a, b)
        # one executable served every row of both forms
        assert len(eng.exe.compile_counts()) == len(runs) + 1
    finally:
        eng.close()


def test_spec_group_fills_the_draft_pool_row_by_row(served_scope):
    """A speculative engine prefills target and draft request by
    request: after a group of three the draft's pools (and the
    target's) hold, byte for byte, what each request's own two
    dispatches leave."""
    scope = served_scope[0]
    with fluid.scope_guard(scope):
        copy_weights_as_draft(scope)
    eng = _group_engine(scope, draft_cfg=CFG, prompt_buckets=(8,),
                        max_new_tokens=6, gamma=3)
    try:
        assert eng.warmup()["programs"] == 4   # 2 prefills, decode, spec
        rng = np.random.RandomState(13)
        prompts = _prompts(3, rng, lo=3, hi=8)
        # max_new=1 retires at the first token: no speculative round
        # writes behind the prefills
        reqs = _queue_together(eng, prompts, max_new=1)
        first = [int(r.result(120)[0]) for r in reqs]
        eng._stop.set()
        eng._worker.join(10.0)
        eng.assert_no_recompiles()
        assert eng.stats()["prefill_dispatch_total"] == 3
        target, draft = (_pool_bytes(eng._pools),
                         _pool_bytes(eng._draft_pools))
        assert all(d[:, 1:].any() for d in draft)
        # each request alone, into zeroed pools, at the pages a fresh
        # allocator granted it (1.., in order)
        eng._pools = [jnp.zeros_like(p) for p in eng._pools]
        eng._draft_pools = [jnp.zeros_like(p) for p in eng._draft_pools]
        page = 1
        for i, p in enumerate(prompts):
            tokens = np.zeros((1, 8), np.int64)
            tokens[0, :p.size] = p
            need = eng._pages_needed(p.size, 1)
            table = np.zeros((1, eng.pages_per_seq), np.int32)
            table[0, :need] = page + np.arange(need)
            page += need
            lens = np.asarray([p.size], np.int32)
            assert int(eng._run_prefill_program(
                8, tokens, lens, table)[0]) == first[i]
            eng._run_draft_prefill_program(8, tokens, lens, table)
        # page 0 is the null page: the warm-up's dummy rows wrote there
        for a, b in zip(draft + target, _pool_bytes(
                eng._draft_pools + eng._pools)):
            np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
    finally:
        eng.close()


def test_submit_validation(engine):
    with pytest.raises(BucketError):
        engine.submit(np.zeros(9, np.int64))      # > largest bucket
    with pytest.raises(ValueError):
        engine.submit(np.zeros(0, np.int64))
    with pytest.raises(ValueError):
        engine.submit(np.zeros(4, np.int64), max_new=99)


# ---------------------------------------------------------------------
# page pool under pressure: exhaustion, reuse, deadlines, eos
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def tight_engine(served_scope):
    """Pool sized for ONE active request (3 usable pages), so admission
    has to wait for retirement and pages get reused immediately."""
    scope = served_scope[0]
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=8,
                            decode_block=3, prefill_batch=1,
                            n_pages=4, default_timeout_s=120.0))
    eng.warmup()
    yield eng
    eng.close()


def test_never_fits_sheds_with_queue_full_semantics(served_scope):
    """A request that can NEVER fit the page pool sheds immediately at
    submit with QueueFullError semantics (PagesExhaustedError) — no
    queueing, no compute. Program building is trace-free, so this
    engine costs no XLA compiles."""
    eng = DecodeEngine(
        CFG, scope=served_scope[0], place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=8, n_pages=3,
                            decode_block=3, prefill_batch=1),
        auto_start=False)
    assert eng._pages_needed(8, 6) > eng.allocator.usable_pages
    with pytest.raises(PagesExhaustedError):
        eng.submit(np.zeros(8, np.int64), max_new=6, timeout=5)
    with pytest.raises(QueueFullError):   # the same typed contract
        eng.submit(np.zeros(8, np.int64), max_new=6, timeout=5)
    assert eng.stats()["shed_total"] == 2
    eng.close()


def test_transient_exhaustion_queues_and_reuses_pages(tight_engine):
    """Three requests through a one-request pool: admission waits for
    pages, retirement frees them, and the request that reuses a
    retired request's pages produces its run-alone tokens exactly
    (stale page contents are unobservable behind the length mask)."""
    rng = np.random.RandomState(2)
    # prompts that write a second page in their first decode dispatch:
    # two of them are four pages, over the pool from admission on
    prompts = _prompts(3, rng, lo=6, hi=8)
    reqs = [tight_engine.submit(p, max_new=4, timeout=120)
            for p in prompts]
    together = [r.result(120) for r in reqs]
    alone = [tight_engine.generate(p, max_new=4, timeout=120)
             for p in prompts]
    for a, b in zip(together, alone):
        np.testing.assert_array_equal(a, b)
    st = tight_engine.stats()
    assert st["page_wait_total"] >= 1     # admission actually waited
    assert st["pages_in_use"] == 0
    tight_engine.assert_no_recompiles()


def test_deadline_in_queue_times_out(tight_engine):
    """A request whose deadline expires while it waits for pages is
    swept with RequestTimeoutError, not served stale."""
    rng = np.random.RandomState(3)
    long_req = tight_engine.submit(
        rng.randint(0, CFG.vocab_size, (8,)).astype(np.int64),
        max_new=6, timeout=120)
    starved = tight_engine.submit(
        rng.randint(0, CFG.vocab_size, (8,)).astype(np.int64),
        max_new=6, timeout=0.001)
    with pytest.raises(RequestTimeoutError):
        starved.result(30)
    assert len(long_req.result(120)) == 6


def test_eos_retires_early(served_scope):
    """eos_id retires a sequence at the step it is emitted; the
    surviving prefix equals the no-eos run's prefix."""
    scope = served_scope[0]
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, CFG.vocab_size, (5,)).astype(np.int64)
    plain = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=8, page_size=8,
                            decode_block=2, prefill_batch=1,
                            default_timeout_s=120.0))
    try:
        full = plain.generate(prompt, max_new=8, timeout=120)
    finally:
        plain.close()
    eos = int(full[3])                    # force an eos mid-stream
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=8, page_size=8,
                            decode_block=2, prefill_batch=1,
                            eos_id=eos, default_timeout_s=120.0))
    try:
        got = eng.generate(prompt, max_new=8, timeout=120)
    finally:
        eng.close()
    first = int(np.where(full == eos)[0][0])
    np.testing.assert_array_equal(got, full[:first + 1])
    assert got[-1] == eos


# ---------------------------------------------------------------------
# speculative engine mode
# ---------------------------------------------------------------------

def test_spec_mode_matches_greedy(served_scope, engine):
    """Speculative decoding as an engine mode (perfect draft): token
    streams identical to the plain engine, rows advancing at full
    gamma+1 acceptance."""
    scope = served_scope[0]
    with fluid.scope_guard(scope):
        copy_weights_as_draft(scope)
    rng = np.random.RandomState(5)
    prompts = _prompts(6, rng, lo=3, hi=8)
    greedy = [engine.generate(p, max_new=6, timeout=120)
              for p in prompts]
    spec = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(), draft_cfg=CFG,
        config=DecodeConfig(max_batch=4, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=8, gamma=3,
                            prefill_batch=2, default_timeout_s=120.0))
    try:
        spec.warmup()
        reqs = [spec.submit(p, max_new=6, timeout=120) for p in prompts]
        got = [r.result(120) for r in reqs]
        spec.assert_no_recompiles()
        st = spec.stats()
    finally:
        spec.close()
    for a, b in zip(got, greedy):
        np.testing.assert_array_equal(a, b)
    # perfect draft ⇒ every round advances gamma+1 tokens
    assert st["spec_rounds_total"] > 0
    assert (st["spec_tokens_accepted_total"]
            == (spec.config.gamma + 1) * st["spec_rounds_total"])


# ---------------------------------------------------------------------
# int8 weight serving through the paged programs
# ---------------------------------------------------------------------

def test_quantized_engine_matches_quantized_generator(served_scope):
    """quantize=True serves the same W8A8 scope (and the same tokens)
    as build_llama_generator(quantize=True) — qmat is shared."""
    base_scope, exe, _, _ = served_scope
    scope = fluid.Scope()
    for name in base_scope.keys():
        scope.set(name, np.asarray(base_scope.find_var(name)))
    with fluid.scope_guard(scope):
        quantize_generator_weights(scope)
    qgen, qstart = fluid.Program(), fluid.Program()
    with fluid.program_guard(qgen, qstart):
        ptok = fluid.layers.data(name="qtok", shape=[1, 6],
                                 dtype="int64", append_batch_size=False)
        qout = build_llama_generator(CFG, ptok, max_new_tokens=4,
                                     quantize=True)
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, CFG.vocab_size, (1, 6)).astype(np.int64)
    with fluid.scope_guard(scope):
        ref = np.asarray(exe.run(qgen, feed={"qtok": prompt},
                                 fetch_list=[qout], mode="test")[0])
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=4, page_size=8,
                            decode_block=2, prefill_batch=1,
                            quantize=True, default_timeout_s=120.0))
    try:
        got = eng.generate(prompt[0], max_new=4, timeout=120)
    finally:
        eng.close()
    np.testing.assert_array_equal(got, ref[0, 6:])


# ---------------------------------------------------------------------
# chaos: worker crash loses nothing
# ---------------------------------------------------------------------

def test_worker_crash_zero_lost_requests(served_scope):
    """serving_worker_crash mid-stream: every submitted request settles
    with a result or a typed error (nothing hangs, nothing is silently
    dropped), and start() revives the engine for new traffic."""
    scope = served_scope[0]
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=8,
                            decode_block=2, prefill_batch=1,
                            watchdog_interval_s=0.02,
                            default_timeout_s=30.0))
    try:
        eng.warmup()
        rng = np.random.RandomState(7)
        prompts = _prompts(6, rng, lo=3, hi=8)
        # arm against a deterministic submit-count barrier: the
        # worker's idle queue polls also pass the fault point, so a
        # bare at= clock races the submission loop (on a fast host the
        # crash could fire against an empty or already-drained engine
        # and the drill never happens). The barrier holds the clock
        # until all 6 admissions are in, then fires 2 worker loop
        # iterations later — guaranteed mid-stream on any host.
        faultinject.arm("serving_worker_crash", at=2,
                        after=("decode_submit", 6))
        reqs = [eng.submit(p, max_new=6, timeout=30) for p in prompts]
        outcomes = []
        deadline = time.monotonic() + 30
        for r in reqs:
            assert r.wait(max(deadline - time.monotonic(), 0.1)), \
                "request neither completed nor failed — LOST"
            try:
                outcomes.append(("ok", r.result(0)))
            except WorkerDiedError:
                outcomes.append(("died", None))
        faultinject.disarm()
        assert any(o == "died" for o, _ in outcomes)
        assert eng.stats()["worker_died_total"] == 1
        assert eng.allocator.in_use == 0      # crash freed every page
        # revival: the engine serves again after start()
        eng.start()
        got = eng.generate(prompts[0], max_new=4, timeout=30)
        assert len(got) == 4
    finally:
        faultinject.disarm()
        eng.close()


# ---------------------------------------------------------------------
# the pools are donated to every dispatch; a consumed pool is lost
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def donating_engines(served_scope):
    """Every program an engine has, over two engines (a speculative
    engine has no chunk program): neither has a worker."""
    scope = served_scope[0]
    with fluid.scope_guard(scope):
        copy_weights_as_draft(scope)
    engines = {"chunked": _group_engine(scope, chunk_size=4),
               "spec": _group_engine(scope, draft_cfg=CFG, gamma=3)}
    yield engines
    for eng in engines.values():
        eng.close()


@pytest.mark.parametrize("which,label", [
    ("chunked", "prefill_4"), ("chunked", "chunk"), ("chunked", "decode"),
    ("spec", "prefill_4"), ("spec", "prefill_8"),
    ("spec", "draft_prefill_4"), ("spec", "draft_prefill_8"),
    ("spec", "decode"), ("spec", "spec")])
def test_every_program_consumes_the_pools_it_is_fed(donating_engines,
                                                    which, label):
    """Each program's dispatch deletes the pools it was fed, leaves the
    engine live ones, and gives the tokens and pool bytes of the same
    lowered program under a jit that donates nothing; XLA aliases all of
    the pools' bytes."""
    eng = donating_engines[which]
    if which == "chunked":
        # bucket 8 is beyond chunk_size 4: those prompts go in slices
        assert sorted(eng._bundles()) == ["chunk", "decode", "prefill_4"]
    else:
        assert len(eng._bundles()) == 6
    b, arrays, fed = check_dispatch_donates(eng, label, CFG.vocab_size)
    pools = eng._pools_of(b)
    assert len(pools) == len(fed)
    assert aliased_bytes(eng, b, arrays, pools) \
        >= sum(p.nbytes for p in pools)
    assert not any(p.is_deleted() for p in pools)    # a lowering only


def _fail_once_consumed(eng, monkeypatch, program, exc):
    """``eng``'s next dispatch of ``program`` runs, consumes its pools
    and THEN raises ``exc``: once. Returns the list of calls it saw."""
    run, calls = eng.exe.run, []

    def failing(prog, *args, **kw):
        outs = run(prog, *args, **kw)
        if prog is program and not calls:
            calls.append(prog)
            raise exc
        return outs

    monkeypatch.setattr(eng.exe, "run", failing)
    return calls


def test_a_dispatch_that_fails_with_its_pools_consumed_loses_them(
        served_scope, engine, monkeypatch):
    """The decode dispatch raises after the executable took the pools: a
    TRANSIENT error, which the policy would retry. Every live slot fails
    with PoolsLostError, nothing is retried, the pools are zeroed ones of
    the right shapes, and the next request gets a fresh engine's tokens."""
    eng = _group_engine(served_scope[0], retry_policy=RetryPolicy(
        max_attempts=3, initial_backoff=0.0))
    try:
        eng.warmup()
        rng = np.random.RandomState(21)
        prompts = _prompts(3, rng, lo=3, hi=8)
        want = [engine.generate(p, max_new=6, timeout=120) for p in prompts]
        calls = _fail_once_consumed(
            eng, monkeypatch, eng.programs.decode["program"],
            TransientDeviceError("UNAVAILABLE: lost after the launch"))
        before = eng.stats()
        reqs = _queue_together(eng, prompts, max_new=6)
        for r in reqs:
            with pytest.raises(PoolsLostError) as err:
                r.result(120)
            assert isinstance(err.value.__cause__, TransientDeviceError)
        assert len(calls) == 1
        after = eng.stats()
        assert after["pools_lost_total"] - before["pools_lost_total"] == 1
        assert after["retries_total"] == before["retries_total"]
        assert after["errors_total"] - before["errors_total"] == 3
        assert after["decode_batches_total"] == before["decode_batches_total"]
        assert eng.allocator.in_use == 0 and eng._active() == []
        assert [(tuple(p.shape), p.dtype) for p in eng._pools] \
            == [(tuple(shape), np.dtype(dtype))
                for shape, dtype in eng.programs.pool_specs]
        # the engine serves on, from a cache that holds nothing stale
        for p, w in zip(prompts, want):
            np.testing.assert_array_equal(
                eng.generate(p, max_new=6, timeout=120), w)
        eng.assert_no_recompiles()
        assert eng.stats()["pools_lost_total"] \
            - before["pools_lost_total"] == 1
    finally:
        eng.close()


def test_a_prefill_that_loses_the_pools_fails_the_slots_that_held_pages(
        served_scope, engine, monkeypatch):
    """A prefill's failure after consumption is not that request's alone:
    the slots that were decoding lose their cache with it. The pools are
    found zeroed right after the failed dispatch, not at the next one."""
    eng = _group_engine(served_scope[0], prefill_batch=1)
    try:
        eng.warmup()
        rng = np.random.RandomState(22)
        first, second = _prompts(2, rng, lo=5, hi=8)
        calls, decoding = [], []
        run = eng.exe.run
        prefill = eng.programs.prefill[8]["program"]

        def failing(prog, *args, **kw):
            outs = run(prog, *args, **kw)
            calls.append(prog)
            if prog is prefill and calls.count(prefill) == 2:
                decoding.extend(slot.req for _, slot in eng._active())
                raise RuntimeError("INTERNAL: the program failed")
            return outs

        monkeypatch.setattr(eng.exe, "run", failing)
        reqs = _queue_together(eng, [first, second], max_new=8)
        for r in reqs:
            with pytest.raises(PoolsLostError):
                r.result(120)
        assert decoding == reqs[:1]        # the first held a slot by then
        stats = eng.stats()
        assert stats["pools_lost_total"] == 1
        assert eng.allocator.in_use == 0
        assert not any(p.is_deleted() for p in eng._pools)
        np.testing.assert_array_equal(
            eng.generate(first, max_new=6, timeout=120),
            engine.generate(first, max_new=6, timeout=120))
    finally:
        eng.close()


def test_a_consumed_pool_put_back_is_replaced_in_silence_when_none_is_live(
        served_scope):
    """Someone keeps the arrays an engine held, dispatches, and puts them
    back: with no slot live the next dispatch starts from zeroed pools,
    counted, and raises nothing."""
    eng = _group_engine(served_scope[0])
    try:
        kept = list(eng._pools)
        _, arrays, _ = check_dispatch_donates(eng, "decode", CFG.vocab_size)
        assert not any(p.is_deleted() for p in kept)   # noise was fed
        eng._run_decode_program(*arrays)
        eng._pools[:] = [eng._pools[0], kept[1]]
        eng._run_decode_program(*arrays)               # consumes kept[1]
        eng._pools[:] = kept
        assert eng.stats()["pools_lost_total"] == 0
        out = eng._run_decode_program(*arrays)
        assert eng.stats()["pools_lost_total"] == 1
        fresh = _group_engine(served_scope[0])
        try:
            np.testing.assert_array_equal(
                out, fresh._run_decode_program(*arrays))
            for a, b in zip(_pool_bytes(eng._pools),
                            _pool_bytes(fresh._pools)):
                np.testing.assert_array_equal(a, b)
        finally:
            fresh.close()
    finally:
        eng.close()


def test_an_injected_device_error_still_retries_with_the_pools_intact(
        served_scope, engine):
    """serving_device_error fires BEFORE Executor.run: the pools were not
    taken, the retry finds them, and nothing is lost."""
    eng = _group_engine(served_scope[0], retry_policy=RetryPolicy(
        max_attempts=3, initial_backoff=0.0))
    try:
        eng.warmup()
        rng = np.random.RandomState(23)
        prompts = _prompts(2, rng, lo=3, hi=8)
        reqs = _queue_together(eng, prompts[:1], max_new=6)
        reqs[0].result(120)
        pools = _pool_bytes(eng._pools)
        faultinject.arm("serving_device_error", at=1, times=2)
        try:
            got = eng.generate(prompts[1], max_new=6, timeout=120)
        finally:
            faultinject.disarm()
        stats = eng.stats()
        assert stats["retries_total"] == 2
        assert stats["pools_lost_total"] == 0 and stats["errors_total"] == 0
        np.testing.assert_array_equal(
            got, engine.generate(prompts[1], max_new=6, timeout=120))
        # what the first request left is still in the pools' other pages
        assert any((a != 0).any() for a in pools)
    finally:
        faultinject.disarm()
        eng.close()


@pytest.mark.parametrize("which", ["chunked", "spec"])
def test_pools_consumed_equals_the_dispatches_after_a_mixed_run(
        served_scope, which):
    """Every dispatch consumed its pools: the counter is the sum of the
    dispatch counters (a speculative engine's prefill is two dispatches,
    the target's and the draft's), so a donation JAX dropped would show
    as a shortfall."""
    scope = served_scope[0]
    with fluid.scope_guard(scope):
        copy_weights_as_draft(scope)
    over = dict(chunk_size=4) if which == "chunked" \
        else dict(draft_cfg=CFG, gamma=3)
    eng = _group_engine(scope, **over)
    try:
        warm = eng.warmup()
        before = eng.stats()
        assert before["pools_consumed_total"] == warm["programs"]
        eng.start()
        rng = np.random.RandomState(24)
        reqs = [eng.submit(p, max_new=int(rng.randint(1, 7)), timeout=120)
                for p in _prompts(9, rng, lo=2, hi=8)]
        for r in reqs:
            r.result(120)
        after = eng.stats()
        delta = {k: after[k] - before[k] for k in after
                 if k.endswith("_total")}
        dispatches = (delta["decode_batches_total"]
                      + delta["chunk_prefill_total"]
                      + delta["prefill_dispatch_total"]
                      * (2 if which == "spec" else 1))
        assert delta["decode_batches_total"] > 0
        assert delta["prefill_dispatch_total"] > 0
        assert (delta["chunk_prefill_total"] > 0) == (which == "chunked")
        assert delta["pools_consumed_total"] == dispatches
        assert after["pools_lost_total"] == 0
        eng.assert_no_recompiles()
    finally:
        eng.close()


def test_two_engines_over_one_scope_keep_their_weights(served_scope):
    """Each engine owns its pools and gives them up; the weights are the
    scope's, shared, and no dispatch of either engine deletes one."""
    scope = served_scope[0]
    a, b = (_group_engine(scope).start() for _ in range(2))
    try:
        rng = np.random.RandomState(25)
        prompts = _prompts(3, rng, lo=3, hi=8)
        first = [a.generate(p, max_new=6, timeout=120) for p in prompts]
        second = [b.generate(p, max_new=6, timeout=120) for p in prompts]
        again = [a.generate(p, max_new=6, timeout=120) for p in prompts]
        for x, y, z in zip(first, second, again):
            np.testing.assert_array_equal(x, y)
            np.testing.assert_array_equal(x, z)
        names = [n for n in a.programs.decode["program"].global_block().vars
                 if scope.find_var(n) is not None]
        assert names and not any(
            scope.find_var(n).is_deleted() for n in names)
        assert a.stats()["pools_lost_total"] == 0
        assert b.stats()["pools_lost_total"] == 0
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------

def test_drain_completes_admitted_requests(served_scope):
    scope = served_scope[0]
    eng = DecodeEngine(
        CFG, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=2, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=8,
                            decode_block=2, prefill_batch=1,
                            default_timeout_s=60.0))
    eng.warmup()
    rng = np.random.RandomState(8)
    reqs = [eng.submit(p, max_new=6, timeout=60)
            for p in _prompts(5, rng, lo=3, hi=8)]
    eng.close(drain=True)
    for r in reqs:
        assert len(r.result(1.0)) == 6    # all admitted work finished
    assert eng.stats()["drained_total"] >= 1


# ---------------------------------------------------------------------
# a row takes its pages as it writes them; admission by the residents'
# projected peak (DecodeEngine._admit)
# ---------------------------------------------------------------------

def _walk(pos, left, total, grows, page_size, block):
    """projected_peak by brute force: every dispatch walked, one by one,
    until no growing row is left."""
    peak, j = 0, 0
    while True:
        held, live = 0, False
        for p, n, t, g in zip(pos, left, total, grows):
            if not g:
                held += t
            elif j < -(-n // block):
                held += min(t, -(-(p + (j + 1) * block) // page_size))
                live = True
        peak = max(peak, held)
        if not live:
            return peak
        j += 1


@pytest.mark.parametrize("seed", range(8))
def test_projected_peak_is_the_walk_of_every_dispatch(seed):
    """The projection, evaluated at the rows' last dispatches alone,
    against a walk of every dispatch: random prompts, answers and
    progress, rows that grow and rows that keep a whole reservation, a
    row with nothing left to emit, and no row at all."""
    rng = np.random.RandomState(100 + seed)
    assert projected_peak([], [], [], [], 16, 4) == 0
    for _ in range(50):
        n = int(rng.randint(1, 13))
        page_size = int(rng.choice([1, 4, 8, 16]))
        block = int(rng.choice([1, 2, 3, 4]))
        prompt = rng.randint(1, 41, n)
        max_new = rng.randint(1, 41, n)
        emitted = np.asarray([rng.randint(1, m + 1) for m in max_new])
        pos, left = prompt + emitted - 1, max_new - emitted
        total = -(-(prompt + max_new + block) // page_size)
        grows = rng.rand(n) < 0.7
        assert projected_peak(pos, left, total, grows, page_size, block) \
            == _walk(pos, left, total, grows, page_size, block)
    # three rows of four pages each, one dispatch from their ends, and a
    # newcomer that will not reach its fourth page before they are gone
    assert projected_peak([14, 14, 14, 4], [1, 1, 1, 12], [4] * 4,
                          [True] * 4, 4, 2) == 4 * 3 + 2


def _tight(scope, **over):
    """Eight slots over a pool of three longest requests (11 pages of 4
    each), no worker yet: what is queued before ``start()`` meets one
    admission pass."""
    conf = dict(max_batch=8, prompt_buckets=(8, 16), max_new_tokens=24,
                page_size=4, decode_block=2, prefill_batch=2,
                n_pages=3 * 11 + 1, max_queue=32)
    return _group_engine(scope, **dict(conf, **over))


def _watch(eng, at=None):
    """Records, inside every decode (or speculative) dispatch of ``eng``,
    (rows live, requests queued, [(sequence pages held, positions they
    must cover, grows_to, request) a live row]); ``at(n, slots)`` is
    called in the n-th dispatch, on the worker's thread."""
    seen = []

    def watched(run):
        def dispatch(*args, **kw):
            slots = [s for s in eng.slots if s is not None]
            seen.append((len(slots), len(eng._queue), [
                (len(s.held["sequence"]),
                 s.pos + eng.config.decode_block, s.grows_to, s.req)
                for s in slots]))
            if at is not None:
                at(len(seen), slots)
            return run(*args, **kw)
        return dispatch

    eng._run_decode_program = watched(eng._run_decode_program)
    eng._run_spec_program = watched(eng._run_spec_program)
    return seen


def _books_balance(eng, seen):
    """After a run: no growth ever found the pool empty, every page is
    back, and in every dispatch a growing row held the pages that
    dispatch wrote and not one more."""
    st = eng.stats()
    assert st["page_stall_total"] == 0
    assert st["pages_in_use"] == 0 and eng._active() == []
    for _, _, rows in seen:
        for held, reach, grows_to, _ in rows:
            if grows_to is not None:
                assert held == min(grows_to, eng.allocator.pages_for(reach))
    return st


@pytest.fixture(scope="module")
def unequal(served_scope):
    """Fourteen requests of unequal lengths, and each one's tokens
    alone (a pool that holds every slot's longest request)."""
    rng = np.random.RandomState(21)
    prompts = _prompts(14, rng, lo=6, hi=16)
    news = [int(n) for n in rng.randint(10, 25, len(prompts))]
    roomy = _tight(served_scope[0], n_pages=None)
    try:
        roomy.start()
        alone = [roomy.generate(p, max_new=n, timeout=120)
                 for p, n in zip(prompts, news)]
        st = roomy.stats()
    finally:
        roomy.close()
    # a roomy pool never computes a projection, and never waits
    assert st["page_wait_total"] == 0 == st["page_stall_total"]
    assert st["admit_projection_refusals_total"] == 0
    assert st["pages_grown_total"] > 0
    return prompts, news, alone


def test_rows_that_take_pages_as_they_write_them_hold_more_rows(
        served_scope, unequal):
    """A pool of three whole reservations under eight slots: every
    request returns its tokens alone, no growth ever stalls, every page
    comes back, and a decode dispatch holds more rows than the same
    requests do where every row keeps its whole reservation (the rule
    before: ``_grows`` false for all)."""
    prompts, news, alone = unequal
    mean_rows, stats = {}, {}
    for rule in ("grows", "whole"):
        eng = _tight(served_scope[0])
        if rule == "whole":
            eng._grows = lambda r: False
        seen = _watch(eng)
        try:
            reqs = [eng.submit(p, max_new=n, timeout=120)
                    for p, n in zip(prompts, news)]
            eng.start()
            got = [r.result(120) for r in reqs]
            eng.close(drain=True)
            stats[rule] = _books_balance(eng, seen)
        finally:
            eng.close()
        for a, b in zip(got, alone):
            np.testing.assert_array_equal(a, b)
        # while a request was waiting, so the drain's tail is left out
        bound = [rows for rows, queued, _ in seen if queued]
        mean_rows[rule] = sum(bound) / len(bound)
        assert all(held <= 33 for held in (
            sum(h for h, _, _, _ in rows) for _, _, rows in seen))
    new, old = stats["grows"], stats["whole"]
    assert new["pages_grown_total"] > 0 == old["pages_grown_total"]
    assert new["admit_projection_refusals_total"] > 0
    assert new["page_wait_total"] > 0 and old["page_wait_total"] > 0
    assert old["admit_projection_refusals_total"] == 0
    assert mean_rows["grows"] > mean_rows["whole"] + 0.5
    assert new["decode_batches_total"] < old["decode_batches_total"]
    assert new["shed_total"] == 0 == new["errors_total"]


@pytest.mark.parametrize("how", ["eos", "deadline", "drain"])
def test_an_answer_that_ends_early_only_frees_pages_sooner(
        served_scope, unequal, how):
    """``eos_id`` ending answers early, a deadline expiring mid-answer
    and ``close(drain=True)`` all keep the invariant (no growth stalls)
    and free every page; whoever finishes returns its tokens alone."""
    prompts, news, alone = unequal
    want, over, late = list(alone), {}, []
    if how == "eos":
        # a token inside the longest answer, not its last: that answer
        # ends at it, and so does every other answer that emits it
        eos = int(max(alone, key=len)[3])
        over["eos_id"] = eos
        want = [a[:list(a).index(eos) + 1] if eos in a else a
                for a in alone]
        assert any(len(w) < len(a) for w, a in zip(want, alone))

    def expire(n, slots):
        if n == 3:                        # mid-answer, on the worker
            late.append(max(slots, key=lambda s: s.req.max_new).req)
            late[0].deadline = time.monotonic() - 1.0

    eng = _tight(served_scope[0], **over)
    seen = _watch(eng, expire if how == "deadline" else None)
    try:
        reqs = [eng.submit(p, max_new=n, timeout=120)
                for p, n in zip(prompts, news)]
        eng.start()
        if how == "drain":
            eng.close(drain=True)         # everything queued is finished
            assert all(r.done() for r in reqs)
        assert all(r.wait(120) for r in reqs)
        for r, w in zip(reqs, want):
            if r in late:
                with pytest.raises(RequestTimeoutError):
                    r.result(120)
            else:
                np.testing.assert_array_equal(r.result(120), w)
        eng.close(drain=True)
        st = _books_balance(eng, seen)
    finally:
        eng.close()
    assert st["pages_grown_total"] > 0 and st["page_wait_total"] > 0
    assert st["timeouts_total"] == len(late) == (how == "deadline")


@pytest.mark.parametrize("who", ["draft", "chunk", "handoff",
                                 "prefill_only"])
def test_who_keeps_a_whole_reservation(served_scope, who):
    """An engine with a draft (a speculative round advances rows
    unequally), a chunk-path request (its decode starts an unknown
    number of dispatches later), a handoff import and a ``prefill_only``
    request (their pages travel whole) hold from admission what
    ``_pages_needed`` says, as before, and never grow."""
    scope = served_scope[0]
    over = {}
    if who == "draft":
        with fluid.scope_guard(scope):
            copy_weights_as_draft(scope)
        over = dict(draft_cfg=CFG, gamma=3)
    elif who == "chunk":
        over = dict(chunk_size=4)
    rng = np.random.RandomState(31)
    prompt = rng.randint(0, CFG.vocab_size, (9,)).astype(np.int64)
    plain = _tight(scope, n_pages=None)
    eng = _tight(scope, **over)
    seen = _watch(eng)
    try:
        plain.start(), eng.start()
        alone = plain.generate(prompt, max_new=12, timeout=120)
        need = eng._pages_needed(prompt.size, 12)
        if who == "prefill_only":
            blob = eng.submit(prompt, max_new=12,
                              prefill_only=True).result(120)
            assert len(blob["pages"]) == need and not seen
            got = plain.import_handoff(blob).result(120)
        elif who == "handoff":
            got = eng.import_handoff(plain.submit(
                prompt, max_new=12, prefill_only=True).result(120)
            ).result(120)
        else:
            got = eng.generate(prompt, max_new=12, timeout=120)
        np.testing.assert_array_equal(got, alone)
        assert eng.stats()["chunk_prefill_total"] == 3 * (who == "chunk")
        assert eng.stats()["spec_rounds_total"] > 0 or who != "draft"
        st = _books_balance(eng, seen)
    finally:
        plain.close(), eng.close()
    assert seen or who == "prefill_only"
    for _, _, rows in seen:
        assert [(held, grows_to) for held, _, grows_to, _ in rows] \
            == [(need, None)]
    assert st["pages_grown_total"] == 0


# ---------------------------------------------------------------------
# the decode-shape-hazard verifier lint (analysis/lints.py)
# ---------------------------------------------------------------------

def test_decode_shape_hazard_lint_fires_on_growing_concat():
    from paddle_tpu.analysis import verify_program
    p, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(p, s):
        seq = fluid.layers.data(name="seq", shape=[-1, -1],
                                dtype="int64", append_batch_size=False)
        nxt = fluid.layers.data(name="nxt", shape=[-1, 1],
                                dtype="int64", append_batch_size=False)
        grown = fluid.layers.concat([seq, nxt], axis=1)
    diags = [d for d in verify_program(p, fetch_list=[grown])
             if d.code == "decode-shape-hazard"]
    assert len(diags) == 1
    assert diags[0].level == "warning"
    assert "recompiles" in diags[0].message


def test_decode_shape_hazard_lint_quiet_on_static_shapes():
    from paddle_tpu.analysis import verify_program
    p, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(p, s):
        a = fluid.layers.data(name="a", shape=[-1, 4], dtype="float32",
                              append_batch_size=False)
        b = fluid.layers.data(name="b", shape=[-1, 4], dtype="float32",
                              append_batch_size=False)
        out = fluid.layers.concat([a, b], axis=1)
    assert not [d for d in verify_program(p, fetch_list=[out])
                if d.code == "decode-shape-hazard"]
