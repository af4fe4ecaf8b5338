"""DecodeEngine — continuous batching for autoregressive LLM decode.

The batching engine (engine.py) coalesces fixed-shape requests: right
for classifiers, wrong for decode, where a batch member finishes when
IT emits eos, not when its peers do. This engine schedules at
**iteration level** (Orca/vLLM style, under this repo's
one-executable-per-program rule): every step, queued prompts are
admitted into free slots of a fixed ``max_batch``-wide decode program,
finished sequences retire and free their slots, and the XLA executable
never changes shape — request churn is pure host-side integer
bookkeeping over a paged KV cache (kv_pages.py).

The step programs (models/llama.py build_llama_paged_programs):

- **prefill-into-slot** — one program per declared prompt-length
  bucket, batch 1: runs the prompt through the stack, writes its KV
  into the slot's pages, returns the first greedy token (TTFT is
  measured here).
- **decode-step** — ONE program at [max_batch] that advances every
  slot ``decode_block`` tokens per dispatch. Inactive slots ride along
  masked (null page table, outputs discarded); each row's math depends
  only on its own row and pages, so a request's greedy tokens are
  bit-identical alone or co-scheduled — the same
  numerics-never-depend-on-peers discipline as PR 3's signature
  grouping, enforced structurally instead of by grouping.
- **spec-step** (``draft_cfg``) — speculative decoding as an engine
  mode: per round the draft proposes ``gamma`` tokens per slot and the
  target verifies them in one forward, with PER-ROW acceptance (rows
  advance at their own rate; the fused llama_spec_generate op is
  batch-lockstep).

Hardening is the PR 3/4 machinery at request level: bounded admission
(QueueFullError / PagesExhaustedError), per-request deadlines swept to
RequestTimeoutError, engine circuit breaker, HealthMonitor + watchdog
(worker death fails everything pending with WorkerDiedError — the
``serving_worker_crash`` fault point drills this), graceful
``close(drain=True)``, deadline propagation into dispatch retries, and
``warmup()`` + ``assert_no_recompiles()`` pinning the zero-recompile
steady state. Metrics add TTFT/TPOT windows and token counters —
tools/servebench.py --decode turns them into
``llama_decode_serving_tok_s``.
"""
import itertools
import os
import threading
import time

import numpy as np

from ..core.executor import Executor, global_scope
from .. import profiler
from ..profiler import record_event
from ..resilience import faultinject as _faultinject
from ..resilience.retry import RetryPolicy, default_policy, with_retries
from .batching import (QueueFullError, RequestTimeoutError,
                       ServerClosedError)
from .buckets import BucketError
from .health import (CircuitBreaker, HealthMonitor, HealthState,
                     ServiceUnavailableError, WorkerDiedError)
from .batching import ServingError
from .kv_pages import PageAllocator, PagesExhaustedError
from .metrics import ServingMetrics
from .overload import BrownoutController
from .sched import get_scheduler, priority_rank, PRIORITIES

__all__ = ["DecodeConfig", "DecodeRequest", "DecodeEngine",
           "PoolsLostError"]

_DECODE_COUNTERS = (
    "prefill_total", "decode_batches_total", "generated_tokens_total",
    "retired_total", "spec_rounds_total", "spec_tokens_accepted_total",
    "page_wait_total",
    # chunked prefill + SLO attainment + disaggregation (PR 18):
    # chunk_prefill_total counts chunk DISPATCHES (a long prompt is
    # several); the slo_* counters score each SLO-carrying request
    # once per target half; handoffs count exports (prefill side) and
    # imports (decode side) separately so a disaggregated pool's books
    # balance end to end
    "chunk_prefill_total",
    "slo_ttft_met", "slo_ttft_violated",
    "slo_tpot_met", "slo_tpot_violated",
    "handoff_export_total", "handoff_import_total",
    # overload robustness (PR 19): sheds broken out by priority tier
    # (the strict shed-ordering proof reads these), queue evictions
    # (a higher-priority arrival displacing a queued batch request),
    # and the brownout ladder — engage/revert transitions plus one
    # counter per degradation step so every brownout action is
    # metered and its full revert is checkable
    "shed_interactive_total", "shed_standard_total",
    "shed_batch_total", "evictions_total",
    "brownout_engage_total", "brownout_revert_total",
    "brownout_cap_max_new_total", "brownout_spec_off_total",
    "brownout_chunk_defer_total",
    # the worker's own clock (PR 24), float seconds where the name ends
    # in _s_total. Every instant of the worker's life goes to loop_busy
    # (the loop's body) or loop_idle (the wait with nothing to do);
    # the dispatch sums lie inside busy, each from the call to its
    # tokens on the host, so busy - dispatches is the host time between
    # dispatches. prefill_total counts REQUESTS, prefill_dispatch_total
    # dispatches (one a whole-prompt request); real against padded
    # (``bucket`` a dispatch) prompt tokens is what the bucket's length
    # wastes; queue_wait is each request's own dispatch instant -
    # enqueued_at, summed over the requests prefill_total counts. The
    # pt:engine/* spans share these boundaries.
    "loop_busy_s_total", "loop_idle_s_total",
    # a set-up's two parts inside the engine, each with its span's
    # boundaries: the constructor's program building and pool
    # allocation (pt:engine/build) and warmup() (pt:engine/warmup)
    "engine_build_s_total", "warmup_s_total",
    "decode_dispatch_s_total", "chunk_dispatch_s_total",
    "prefill_dispatch_s_total", "prefill_dispatch_total",
    "prefill_tokens_total", "prefill_padded_tokens_total",
    "queue_wait_s_total",
    # summed on the device by the programs of a model with routed
    # experts or a latent cache, and returned beside a dispatch's tokens
    # (ops/transformer_ops.py PAGED_STATS); 0 for a dense GQA model.
    # Token-expert pairs over the router's whole width, those of them
    # that fell on the experts held here (all, unless the model is one
    # chip's share of an expert-parallel layer) and the fullest held
    # expert's tokens, per routed-layer call, over every dispatch; over
    # decode dispatches alone: routed-layer calls x experts held, the
    # experts of those that a token reached, and the cache positions the
    # active rows attended.
    "moe_assignments_total", "moe_max_load_total",
    "moe_decode_expert_calls_total", "moe_decode_experts_touched_total",
    "latent_tokens_read_total", "moe_held_assignments_total",
    # the pools are donated to every dispatch (PR 32): consumed ticks
    # once a dispatch whose fed pools all came back deleted, so it equals
    # the sum of the dispatch counters unless JAX dropped a donation as
    # unusable; lost ticks once each time the engine found a pool of its
    # own deleted and replaced them all with zeroed ones
    "pools_consumed_total", "pools_lost_total",
    # ticked beside decode_batches_total for every decode dispatch whose
    # program attends its pages through a Pallas kernel (the decode
    # bundle's ``in_place``: plain GQA pools, a mixed model's flat
    # sequence kind or a latent model's one pool, on a backend with the
    # paged kernels; everywhere else the same step calls their jax.numpy
    # reference): equal to decode_batches_total on the chip, 0 on a CPU
    "decode_in_place_total",
    # ticked beside decode_batches_total for every decode dispatch whose
    # program steps its state layers' entries through the Pallas kernel
    # (the decode bundle's ``state_in_kernel``: the selective state-space
    # mixer or the delta rule over a float32 pool of whole tiles, on a
    # backend with the kernel; everywhere else the jax.numpy step): equal
    # to decode_batches_total on the chip for Jamba2 and for Ling's kda
    # layers, 0 on a CPU, for Olmo-Hybrid (a state a lane tile and a half
    # wide) and for a model without such layers
    "state_step_in_kernel_total",
    # ticked once a decode dispatch made in the bundle's ``probe`` form
    # (``_run_decode_program`` called from outside the loop: every step's
    # float32 logits and picks fetched and left under ``kept["decode"]``):
    # how often the costly form ran, 0 over any window of serving
    "decode_probe_dispatches_total",
    # and decode_in_place_total's sibling for prefill: ticked beside
    # prefill_dispatch_total and chunk_prefill_total for every whole-prompt
    # or chunk dispatch
    # whose program folds its attention through the kernel prefill_fold
    # (the bundle's ``attn_in_kernel``: a block-kind model's layers that
    # keep the whole sequence, on a backend with the kernel), to be read
    # against the sum of those two
    "prefill_attn_in_kernel_total",
    # and its sibling for the routed experts: ticked at the same two places
    # for every whole-prompt or chunk dispatch whose program puts its routed
    # layers' sorted pairs through the kernel moe_grouped_rows (the bundle's
    # ``experts_in_kernel``: a window of more rows than the few-rows kernel
    # takes over experts two of which fit the kernel's budget, a share or
    # a whole layer, on a backend with the kernel), to be read against the
    # same sum
    "prefill_experts_in_kernel_total",
    # and the decode step's: ticked beside decode_batches_total for every
    # decode dispatch whose program puts its routed layers through a
    # kernel (the decode bundle's ``experts_in_kernel``: moe_few_rows at
    # most one MXU tile of rows over experts a tile of which fits the
    # kernel's budget, moe_grouped_rows behind the sort at more rows over
    # experts that fit its budget uncut; a share or a whole layer, on a
    # backend with the kernel): equal to decode_batches_total on the chip
    # for agent, reason, mixed, docs, wide and longanswers, 0 on a CPU and
    # without routed experts
    "decode_experts_in_kernel_total",
    # a model with window attention layers (PR 33) has caches of two
    # kinds, and counts on the device, over decode steps, the positions
    # its active rows attended in the layers of each (HYBRID_STATS:
    # layers x rows x positions). The engine's own: each turn of a ring
    # page (a window page whose positions all fell out of the window and
    # were overwritten where they lay), and, once a decode dispatch over
    # the active slots, the bytes of cache they hold (pages of every
    # kind, whole) and the positions resident in them: bytes over
    # positions is what a cached token costs (30,720 B in the cell's
    # model were every layer kept whole). Every model ticks the last
    # two; the first three stay 0 for a model with one cache kind.
    "attn_full_positions_total", "attn_window_positions_total",
    "window_pages_recycled_total",
    "cache_bytes_held_total", "cache_positions_resident_total",
    # a model with state-space layers (PR 39) keeps ONE cache entry a
    # request in each of them, the ``state`` kind, and counts on the
    # device the states its active rows updated over decode steps and the
    # real positions its prefill windows scanned (SSM_STATS: layers x
    # rows | positions). The engine's own: the dispatches that started a
    # request's state from zeros (a whole-prompt prefill, a prompt's first
    # chunk: it equals the requests started, and a request whose entry
    # was not reset would continue its predecessor's), and, beside
    # cache_bytes_held_total (which counts them too), the bytes of the
    # state entries the active slots held. 0 for a model without the kind.
    "ssm_state_updates_total", "ssm_prefill_positions_total",
    "state_resets_total", "state_bytes_held_total",
    # the same two for a model whose state layers are gated delta-rule
    # linear attention (PR 47; DELTA_STATS): a matrix state a head, so an
    # entry is megabytes a layer and a long prompt is its chunk job's to
    # carry through many dispatches
    "delta_state_updates_total", "delta_prefill_positions_total",
    # and for a model whose state layers are gated short convolutions
    # (CONV_STATS): the kind with ONE pool, a tail of two inputs a layer
    "conv_state_updates_total", "conv_prefill_positions_total",
    # and for a model whose state layers are Kimi delta attention (a decay
    # a channel) beside LATENT layers in one stack (KDA_LATENT_STATS): the
    # rule's two under this mixer's name, and the positions the active
    # rows attended in the latent layers' pool over decode steps
    "kda_state_updates_total", "kda_prefill_positions_total",
    "attn_latent_positions_total",
    # a model whose stack is run several times a token (PR 43) keeps a
    # cache layer a pass a layer, and counts on the device, over decode
    # steps, the layer passes its active rows went through and the
    # positions they attended (LOOP_STATS). Such a cache is the first so
    # large that the POOL and not the slots bounds the batch; the engine's
    # own, for every model: the decode dispatches that ran while a request
    # was queued, a slot stood free and the queue's head was refused its
    # pages (page_wait_total counts the refusals, one an admission pass,
    # not the time): beside decode_batches_total it says which of the two,
    # slots or pages, set the batch.
    "loop_layer_passes_total", "loop_positions_attended_total",
    "decode_page_bound_total",
    # a whole-prompt request takes its ``sequence`` pages as it writes
    # them (PR 54), and admission is by the residents' projected peak
    # (``_admit``): the pages rows were granted after their admission,
    # before the decode dispatch that writes them; the admission passes
    # in which the queue's head had its first pages free and the
    # projected peak refused it (a part of page_wait_total); and the
    # growths that found no page, which the rule excludes: MUST read 0
    "pages_grown_total", "admit_projection_refusals_total",
    "page_stall_total")

# how long after a program's end the worker keeps polling before it reads
# the tokens and counters whose host copies set out with the program: the
# copies were measured to land 0.23-0.3 ms behind it (PERF.md section 6,
# PR 31)
_HOST_COPY_S = 4e-4

# priority rank -> the per-class shed counter it lands in
_SHED_BY_RANK = {rank: f"shed_{name}_total"
                 for name, rank in PRIORITIES.items()}


def _env_float(name, default):
    return float(os.environ.get(name, default))


class PoolsLostError(ServingError):
    """The cache pools were consumed by a dispatch that did not hand
    them back (or were deleted under the engine), so every sequence that
    held pages lost its cache. The engine has zeroed pools again and
    serves the next request; this one must be submitted anew. Never
    retried as it stands: there is no cache to continue on."""


class DecodeConfig:
    """Tuning knobs for one decode engine.

    Geometry — fixed at build time, every executable derives from it:
    ``max_batch`` concurrent decode slots; ``prompt_buckets`` declared
    prompt-length pads (one prefill executable each);
    ``max_new_tokens`` the per-request generation cap; ``page_size``
    positions per KV page; ``n_pages`` pool size (None → full
    residency: every slot can hold its longest sequence — smaller
    values overcommit and admission waits until the residents'
    projected peak leaves room, ``DecodeEngine._admit``);
    ``decode_block`` tokens generated per decode dispatch (the
    dispatch-overhead amortizer; admission/retirement happen at block
    boundaries); ``prefill_batch`` the most same-bucket requests one
    admission pass takes from the queue together (it shapes no program:
    every prefill program has one row, and a group is dispatched
    request by request); ``gamma`` draft tokens per speculative round.

    Traffic: ``eos_id`` retires a sequence early (None = generate to
    max_new); ``max_queue`` admission bound; ``default_timeout_s``
    per-request deadline when the caller gives none. Hardening knobs
    mirror ServingConfig (same env vars)."""

    def __init__(self, max_batch=4, prompt_buckets=(16, 32),
                 max_new_tokens=32, page_size=16, n_pages=None,
                 decode_block=4, prefill_batch=4, gamma=4,
                 eos_id=None, quantize=False,
                 max_queue=64, default_timeout_s=30.0,
                 retry_policy=None, breaker_threshold=None,
                 breaker_cooldown_s=None, drain_timeout_s=None,
                 watchdog_interval_s=None, hang_timeout_s=None,
                 chunk_size=None, scheduler=None, brownout=None):
        self.max_batch = int(max_batch)
        self.prompt_buckets = tuple(
            sorted(set(int(b) for b in prompt_buckets)))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError("prompt_buckets must be positive ints")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.page_size = int(page_size)
        self.n_pages = n_pages
        self.decode_block = max(1, int(decode_block))
        self.prefill_batch = max(1, int(prefill_batch))
        self.gamma = max(1, int(gamma))
        self.eos_id = eos_id
        self.quantize = bool(quantize)
        self.max_queue = int(max_queue)
        self.default_timeout_s = default_timeout_s
        self.retry_policy = retry_policy
        self.breaker_threshold = int(
            _env_float("PADDLE_TPU_BREAKER_THRESHOLD", 5)
            if breaker_threshold is None else breaker_threshold)
        self.breaker_cooldown_s = (
            _env_float("PADDLE_TPU_BREAKER_COOLDOWN", 1.0)
            if breaker_cooldown_s is None else float(breaker_cooldown_s))
        self.drain_timeout_s = (
            _env_float("PADDLE_TPU_DRAIN_TIMEOUT", 10.0)
            if drain_timeout_s is None else float(drain_timeout_s))
        self.watchdog_interval_s = (
            _env_float("PADDLE_TPU_WATCHDOG_INTERVAL", 0.1)
            if watchdog_interval_s is None else float(watchdog_interval_s))
        self.hang_timeout_s = (
            _env_float("PADDLE_TPU_HANG_TIMEOUT", 30.0)
            if hang_timeout_s is None else float(hang_timeout_s))
        # chunked prefill: prompts LONGER than chunk_size are prefilled
        # as chunk_size-token slices, one slice per engine iteration,
        # co-scheduled with the decode batch (None = whole-prompt
        # prefill only). scheduler: None/'fifo', 'slo', or an object
        # with order()/admit_now() (serving/sched.py)
        self.chunk_size = None if chunk_size is None else int(chunk_size)
        if self.chunk_size is not None and self.chunk_size < 1:
            raise ValueError(
                f"chunk_size must be >= 1, got {self.chunk_size}")
        self.scheduler = scheduler
        # brownout: None/False = off; True = ladder with defaults; a
        # dict = BrownoutController kwargs, plus the engine-side
        # "queue_target_s" (seconds of queue delay that count as full
        # pressure) and "max_new_cap" (batch-tier max_new under
        # level >= 1; default max_new_tokens // 4)
        self.brownout = brownout


class DecodeRequest:
    """Caller handle for one generation request. Settlement is
    first-writer-wins (the worker and the watchdog can race, exactly
    as in batching.PendingResult). ``result()`` returns the generated
    tokens as a 1-D int64 array (prompt not included; ends at eos_id
    inclusive when one was emitted). ``seq`` is the engine's sequence
    number for the request: the ``req`` attribute of its trace spans."""

    __slots__ = ("prompt", "max_new", "deadline", "enqueued_at",
                 "ttft_s", "slo", "prefill_only", "handoff_state",
                 "seq", "_event", "_result", "_error", "_settle_lock",
                 "_callbacks")

    def __init__(self, prompt, max_new, deadline, enqueued_at,
                 slo=None, prefill_only=False, handoff_state=None,
                 seq=None):
        self.seq = seq
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline
        self.enqueued_at = enqueued_at
        self.slo = slo               # SLOClass or None (best effort)
        self.prefill_only = bool(prefill_only)
        self.handoff_state = handoff_state   # imported KV blob or None
        self.ttft_s = None           # set when the first token lands
        self._event = threading.Event()
        self._result = None
        self._error = None
        self._settle_lock = threading.Lock()
        self._callbacks = []

    def done(self):
        return self._event.is_set()

    def add_done_callback(self, fn):
        """Call ``fn(self)`` exactly once on settlement (result OR
        error); immediately if already settled. Same contract as
        PendingResult.add_done_callback — the router's admission
        accounting hangs off this. Callback exceptions are
        swallowed."""
        with self._settle_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        self._run_callback(fn)

    def _run_callback(self, fn):
        try:
            fn(self)
        except Exception:       # noqa: BLE001 — observer must not break settle
            pass

    def set_result(self, value):
        with self._settle_lock:
            if self._event.is_set():
                return False
            self._result = value
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:           # outside the lock: observers may block
            self._run_callback(fn)
        return True

    def set_error(self, exc):
        with self._settle_lock:
            if self._event.is_set():
                return False
            self._error = exc
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            self._run_callback(fn)
        return True

    def wait(self, timeout=None):
        return self._event.wait(timeout)

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise RequestTimeoutError(
                "result not ready within the wait bound")
        if self._error is not None:
            raise self._error
        return self._result


def projected_peak(pos, left, total, grows, page_size, block):
    """The most ``sequence`` pages the rows hold together at any decode
    dispatch to come, in pure integers. Row ``i`` has ``pos[i]`` positions
    cached and ``left[i]`` tokens still to emit, and every decode dispatch
    advances every row ``block`` positions, so it runs ``d = ceil(left /
    block)`` more dispatches. Where ``grows[i]`` it takes its pages as it
    writes them: during dispatch ``j < d`` (the next is 0) it holds
    ``min(total[i], pages for pos[i] + (j + 1) x block positions)`` and
    from ``j = d`` on nothing; where not, it holds ``total[i]`` at every
    ``j`` (nothing is assumed of when it leaves). The sum only rises
    between two retirements, so it is evaluated at each growing row's
    LAST dispatch and nowhere else."""
    pos, left, total = (np.asarray(x, np.int64).reshape(-1, 1)
                        for x in (pos, left, total))
    grows = np.asarray(grows, bool).reshape(-1, 1)
    if not pos.size:
        return 0
    last = -(-left // block)
    at = np.unique(np.where(grows, np.maximum(last, 1), 1))[None] - 1
    need = np.minimum(total, -(-(pos + (at + 1) * block) // page_size))
    held = np.where(grows, np.where(at < last, need, 0), total)
    return int(held.sum(0).max())


class _Slot:
    """One active decode slot: the request, its page set / table row,
    and the per-sequence scheduler state."""

    __slots__ = ("req", "held", "table", "pos", "cur", "prev",
                 "emitted", "first_token_at", "grows_to")

    def __init__(self, req, held, table, pos, cur, prev, emitted,
                 first_token_at, grows_to=None):
        self.req = req
        # cache kind -> its pages (_alloc); the ``sequence`` kind's list
        # is appended to where the row grows (_grow)
        self.held = held
        self.table = table            # np int32 [pages_per_seq]
        self.pos = pos                # cache length (cur not cached yet)
        self.cur = cur                # last emitted token
        self.prev = prev              # token at pos - 1
        self.emitted = emitted        # generated tokens so far
        self.first_token_at = first_token_at
        # the ``sequence`` pages it may come to hold (_pages_needed) where
        # it takes them as it writes them; None: it holds them all
        self.grows_to = grows_to


class _ChunkJob:
    """One in-progress chunked prefill: the request, its (already
    allocated) page set / table row, and the next slice offset. The
    job reserves a slot index (the slot stays None until the final
    chunk installs it), so free-slot accounting and the decode batch
    never see a half-prefilled sequence."""

    __slots__ = ("req", "held", "table", "off")

    def __init__(self, req, held, table, off=0):
        self.req = req
        self.held = held              # cache kind -> its pages (_alloc)
        self.table = table            # np int32 [pages_per_seq]
        self.off = off                # prompt tokens prefilled so far


class DecodeEngine:
    """Continuous-batching decode server for one model. A model is a
    config with ``build_paged_programs(**geometry)``: the prefill, chunk
    and step programs and the specification of its cache pools
    (models/llama.py PagedDecodePrograms; LlamaConfig and
    models/latent_moe.py LatentMoEConfig are the two there are). The
    engine owns the pools, the page tables and the slots, and knows
    nothing else of the model.

    A model's pools are of the ``sequence`` cache kind (pages for as long
    as the request lives) unless its programs say otherwise
    (``programs.kinds``): the ``window`` kind is a ring of
    ``pages_per_seq`` pages a request, whatever its length, and the
    ``state`` kind ONE entry a request, which no position indexes
    (kv_pages.py). One ``PageAllocator`` serves them all; a slot or chunk
    job holds a MAPPING, cache kind -> its pages of that kind (``held``),
    granted together (but for the ``sequence`` pages a whole-prompt
    request takes as it writes them: ``_admit``) and freed together:
    admission, retirement, shedding
    and the handoff blob cover every kind the model has, and every
    program takes the rows' table of each kind, in ``programs.kinds``'
    order behind the page table. A model with one kind has one table and
    today's programs. The engine knows a kind by what ``programs.kinds``
    says of it and by nothing else, with two exceptions that are the
    kinds' own meaning: a ring's turns are counted
    (``window_pages_recycled_total``), and a dispatch that starts a
    request where the model has the ``state`` kind counts a reset
    (``state_resets_total``): the programs start such a request's state
    from zeros, since an entry, unlike a page, is not hidden by the
    length mask.

    **The pools are the engine's alone and are donated to every
    dispatch**: a program takes ``_pools`` (``_draft_pools``) in, XLA
    writes the new entries into those very buffers, and the engine
    rebinds both lists to what comes back; the arrays it fed are deleted
    by then, so nobody else may hold one across a dispatch. The weights
    are read state of the scope, shared by every engine over it, and are
    never donated. A pool that is found deleted (a dispatch failed after
    the executable took it, or someone put a consumed array back) is
    LOST: ``_replace_lost_pools`` zeroes all pools, fails every slot and
    chunk job that holds pages with ``PoolsLostError`` and counts
    ``pools_lost_total``; no sequence continues on a zeroed cache and
    that dispatch is not retried. ``scope`` must already hold the weights
    the programs name (for Llama the generator layout:
    ``build_llama_generator`` startup, a trained+stacked scope, or a
    ``quantize_generator_weights``'d one; draft weights under
    ``draft.*`` when ``draft_cfg`` — see models/llama.py
    copy_weights_as_draft). The engine never initializes weights.
    ``place=None`` dispatches on the process's default device — the
    chip where there is one; the KV pools are created on that default
    device whatever ``place`` says."""

    RING = "window"         # cache kinds beyond ``sequence``, as the
    STATE = "state"         # allocator and ``programs.kinds`` name them

    def __init__(self, cfg, scope=None, place=None, config=None,
                 draft_cfg=None, auto_start=True, optimize=True):
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.config = config or DecodeConfig()
        c = self.config
        self.scope = scope or global_scope()
        if c.chunk_size is not None and draft_cfg is not None:
            raise NotImplementedError(
                "chunked prefill is a target-model path (the draft "
                "would need its own chunk program); drop chunk_size "
                "or draft_cfg")
        # worst-case positions a slot can touch: a full longest bucket,
        # max_new generated, plus the block/speculation overshoot of
        # the final dispatch before retirement is noticed
        slack = c.decode_block + (c.gamma + 1 if draft_cfg else 0)
        seq_need = c.prompt_buckets[-1] + c.max_new_tokens + slack
        self.pages_per_seq = -(-seq_need // c.page_size)
        n_pages = (c.max_batch * self.pages_per_seq + 1
                   if c.n_pages is None else int(c.n_pages))
        self.allocator = PageAllocator(n_pages, c.page_size)
        self.sched = get_scheduler(c.scheduler)
        # brownout ladder (overload.py): pressure = max(normalized
        # queue delay, breaker-open, page occupancy beyond 90%). The
        # controller decides the level; this engine applies/reverts
        # the effects and counts them.
        self.brownout = None
        self._bo_queue_target_s = 0.5
        self._bo_max_new_cap = max(1, c.max_new_tokens // 4)
        if c.brownout:
            bo_kw = dict(c.brownout) if isinstance(c.brownout, dict) \
                else {}
            self._bo_queue_target_s = float(
                bo_kw.pop("queue_target_s", 0.5))
            self._bo_max_new_cap = int(
                bo_kw.pop("max_new_cap", self._bo_max_new_cap))
            self.brownout = BrownoutController(**bo_kw)
        # the engine's build, one span and one counter of the same
        # boundaries (engine_build_s_total): the model's programs made,
        # rewritten, and the pools allocated
        with record_event("pt:engine/build") as build:
            self.programs = cfg.build_paged_programs(
                max_batch=c.max_batch, page_size=c.page_size,
                n_pages=n_pages, pages_per_seq=self.pages_per_seq,
                prompt_buckets=c.prompt_buckets,
                decode_block=c.decode_block, quantize=c.quantize,
                draft_cfg=draft_cfg, gamma=c.gamma,
                chunk_size=c.chunk_size)
            # further cache kinds, where the model has them (window
            # layers: a ring of pages a request; state-space layers: an
            # entry a request), each with its own pool of page ids
            self.kinds = dict(self.programs.kinds)
            # the ``window`` kind's spec by a name of its own, beside
            # ``_ring_rows``: what benchmark/builders/serve_hybrid.py
            # reads
            self.ring = self.kinds.get(self.RING)
            for kind, spec in self.kinds.items():
                self.allocator.add_kind(kind, spec["n_pages"])
            # which kind each pool is of, and the bytes a page of each
            # kind holds, over all the kind's pools
            self._pool_kind = [
                next((k for k, spec in self.kinds.items()
                      if i in spec["pools"]), PageAllocator.SEQUENCE)
                for i in range(len(self.programs.pool_specs))]
            self._page_bytes = dict.fromkeys(self.allocator.kinds, 0)
            for kind, (shape, dtype) in zip(self._pool_kind,
                                            self.programs.pool_specs):
                self._page_bytes[kind] += int(
                    np.prod([shape[0]] + list(shape[2:]))
                    * np.dtype(dtype).itemsize)
            # graph rewrites on every step program (analysis/optimize.py,
            # proven bit-exact by optcheck): the bundles are private
            # clones, so optimizing in place is safe, and each program's
            # version bump lands BEFORE warmup so the no-recompile pin
            # covers the optimized executables. Failure degrades to the
            # unoptimized bundle.
            self.optimize_reports = {}
            if optimize:
                self._optimize_programs()
            # the cache pools, as the model's programs specify them:
            # donated to every dispatch, and rebound to what it hands back
            self._pools, self._draft_pools = self._zeroed_pools()
            build.note(programs=len(self._bundles()), pool_bytes=sum(
                p.nbytes for p in (*self._pools, *self._draft_pools)))
        # program label -> what its last dispatch returned beside tokens,
        # pools and stats, by name (``logits``, ``picks``), where the
        # model's programs return such: left on the device, for whoever
        # compares them with a reference
        self.kept = {}
        # all retries surface at the serving layer (counted); the inner
        # executor must not also retry. The programs run mode="test" and
        # write no persistable, so the executor has no state to donate:
        # the weights are read state, shared by every engine over this
        # scope, and only the pools are given up (_run_program)
        self.exe = Executor(place,
                            retry_policy=RetryPolicy(max_attempts=1))
        self.metrics = ServingMetrics(extra_counters=_DECODE_COUNTERS)
        self.metrics.incr("engine_build_s_total", build.seconds)
        self._built_at = build.t0 + build.seconds
        self.health = HealthMonitor()
        self.breaker = CircuitBreaker(
            failure_threshold=c.breaker_threshold,
            cooldown_s=c.breaker_cooldown_s)
        self.slots = [None] * c.max_batch
        # slot idx -> _ChunkJob: chunked prefills in flight (the slot
        # itself stays None until the final chunk installs it)
        self._chunk_jobs = {}
        # whether the last admission pass left the queue's head waiting
        # for pages with a slot free (_page_wait)
        self._page_bound = False
        # guards slots + chunk jobs + allocator against the
        # close()/watchdog vs worker race (drain-timeout expiry,
        # worker death)
        self._slots_lock = threading.RLock()
        self._queue = []
        self._qlock = threading.Lock()
        self._cv = threading.Condition(self._qlock)
        self._closed = False          # no new admissions (drain)
        self._warmed = self._warmed_at = None
        self._worker = None
        self._watchdog = None
        self._worker_death_seen = False
        self._stop = threading.Event()
        self._watchdog_stop = threading.Event()
        # chaos hook: per-engine ungraceful worker kill (cluster chaos
        # targets one replica; the global fault point cannot)
        self._crash = threading.Event()
        self._seq = itertools.count(1)       # DecodeRequest.seq
        if auto_start:
            self.start()

    # -- lifecycle -------------------------------------------------------
    def start(self):
        """Start (or restart after a watchdog-declared death) the
        worker + watchdog threads."""
        if self._worker is not None and self._worker.is_alive():
            return self
        # no worker, not a dead one, while the new one is made: a live
        # watchdog that found the stopped thread once ``_stop`` is clear
        # would declare a death and fail what is queued for the new one
        self._worker = None
        self._stop.clear()
        self._crash.clear()
        self._worker_death_seen = False
        self.health.beat()
        worker = threading.Thread(
            target=self._worker_loop, name="paddle-tpu-decode-worker",
            daemon=True)
        worker.start()
        self._worker = worker
        if self._watchdog is None or not self._watchdog.is_alive():
            self._watchdog_stop.clear()
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                name="paddle-tpu-decode-watchdog", daemon=True)
            self._watchdog.start()
        self.health.to(HealthState.READY)
        return self

    def close(self, timeout=5.0, drain=False, drain_timeout=None):
        """``drain=False``: stop admitting, refuse everything pending
        with ServerClosedError, join. ``drain=True``: stop admitting,
        let the worker FINISH every admitted request (bounded by
        ``drain_timeout``, default config.drain_timeout_s); per-request
        deadlines stay live during the drain."""
        worker = self._worker
        if drain and worker is not None and worker.is_alive() \
                and not self._stop.is_set():
            self.health.to(HealthState.DRAINING)
            with self._cv:
                self._closed = True
                self._cv.notify_all()
            budget = (self.config.drain_timeout_s
                      if drain_timeout is None else float(drain_timeout))
            worker.join(max(budget, 0.0))
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._stop.set()
        for req in self._take_pending():
            req.set_error(ServerClosedError("engine closed"))
        if self._worker is not None:
            self._worker.join(timeout)
        self._watchdog_stop.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout)
            self._watchdog = None
        self.health.to(HealthState.STOPPED)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- warmup ----------------------------------------------------------
    def warmup(self):
        """Pre-compile every step executable (each prefill bucket's
        single-row program, the decode step, the spec step) with
        null-page dummy dispatches, then snapshot compile counts for
        assert_no_recompiles(). The steady state after this never
        compiles, no matter how requests churn. Returns the count of
        programs and of executables, the ``seconds`` it took
        (``warmup_s_total``, the ``pt:engine/warmup`` span) and
        ``by_label``, a program: the seconds of its dispatch (its
        ``pt:engine/warm`` span, to the tokens' landing) and the phases
        the compile log holds of it (profiler.compile_log)."""
        seconds = {}

        def warm(label, run, *args):
            with record_event("pt:engine/warm", label=label) as span:
                run(*args)
            seconds[label] = span.seconds

        max_batch = self.config.max_batch
        row = (np.ones((1,), np.int32),
               np.zeros((1, self.pages_per_seq), np.int32),
               *self._kind_tables([None]))
        with record_event("pt:engine/warmup") as whole:
            for bucket in sorted(self.programs.prefill):
                warm(f"prefill_{bucket}", self._run_prefill_program,
                     bucket, np.zeros((1, bucket), np.int64), *row)
                if self.draft_cfg is not None:
                    warm(f"draft_prefill_{bucket}",
                         self._run_draft_prefill_program, bucket,
                         np.zeros((1, bucket), np.int64), *row[:2])
            if self.programs.chunk is not None:
                cs = self.programs.chunk_size
                warm("chunk", self._run_chunk_program,
                     np.zeros((1, cs), np.int64), np.ones((1,), np.int32),
                     np.zeros((1,), np.int32),
                     np.zeros((1, self.pages_per_seq), np.int32),
                     *self._kind_tables([None]))
            # the PLAIN decode program warms even for speculative
            # engines: brownout level 2 (spec_off) switches a live engine
            # to it, and the no-recompile pin must survive that switch
            warm("decode",
                 lambda *a: self._run_decode_program(*a, loop=True),
                 np.zeros((max_batch,), np.int64),
                 np.ones((max_batch,), np.int32),
                 np.zeros((max_batch, self.pages_per_seq), np.int32),
                 *self._kind_tables([None] * max_batch))
            if self.draft_cfg is not None:
                warm("spec", self._run_spec_program,
                     np.zeros((max_batch,), np.int64),
                     np.zeros((max_batch,), np.int64),
                     np.ones((max_batch,), np.int32),
                     np.zeros((max_batch, self.pages_per_seq), np.int32))
        self.metrics.incr("warmup_s_total", whole.seconds)
        self._warmed = self._loop_compiles()
        self._warmed_at = whole.t0 + whole.seconds
        compiles = self.exe.total_compiles()
        self.metrics.incr("warmup_compiles", compiles)
        by_label = {label: {"seconds": round(s, 3)}
                    for label, s in seconds.items()}
        for label, e in self._compiled_since(whole.t0):
            by_label[label].update(
                {k: round(e[k], 3) for k in profiler.COMPILE_PHASES},
                cache_hit=e["cache_hit"])
        return {"programs": len(seconds), "compiles": compiles,
                "seconds": round(whole.seconds, 3), "by_label": by_label}

    def _compiled_since(self, t):
        """(label, entry) for the compile log's entries of this engine's
        programs that closed at or after ``t``: the engine knows a
        program's label, the executor only its uid."""
        labels = {b["program"].uid: label
                  for label, b in self._bundles().items()}
        return [(labels[e["program"]], e)
                for e in profiler.compile_log(since=t)
                if e["program"] in labels]

    def _loop_compiles(self):
        """``exe.compile_counts()`` of the executables the loop dispatches:
        all but the decode bundle's ``probe`` form, which an outside
        caller compiles at its first use and ``warmup`` does not."""
        probe = self.programs.decode.get("probe")
        names = probe and tuple(getattr(v, "name", v)
                                for v in probe["fetch"])
        uid = self.programs.decode["program"].uid
        return {k: n for k, n in self.exe.compile_counts().items()
                if (k[0], k[3]) != (uid, names)}

    def assert_no_recompiles(self):
        """AssertionError if any XLA compile happened after warmup —
        the churn-proof contract — naming what compiled from the
        compile log: label, feed shapes, seconds, ``cache_hit``. No-op
        before warmup. The probe form of the decode program is no part
        of the contract (``_loop_compiles``)."""
        if self._warmed is None:
            return
        now = self._loop_compiles()
        if now != self._warmed:
            what = "; ".join(
                f"{label} in {e['t1'] - e['t0']:.3f} s (cache_hit "
                f"{e['cache_hit']}) for the feeds "
                + " ".join(f"{k}:{v}" for k, v in e["shapes"].items())
                for label, e in self._compiled_since(self._warmed_at))
            raise AssertionError(
                f"decode executables changed after warmup: compiled "
                f"{what or 'nothing the compile log holds'}; "
                f"{self._warmed} -> {now} — a traced shape escaped the "
                "paged-buffer discipline")

    # -- request path ----------------------------------------------------
    def submit(self, prompt, max_new=None, timeout=None, slo=None,
               prefill_only=False, queued_for_s=0.0):
        """Enqueue one prompt; returns a DecodeRequest immediately.
        Rejections (all before any queueing): BucketError (prompt
        outside every declared bucket), PagesExhaustedError (the
        request can NEVER fit the page pool), QueueFullError (shed),
        ServiceUnavailableError (breaker open), ServerClosedError.

        ``slo``: an SLOClass — the scheduler orders admission by its
        TTFT deadline and the attainment counters score against it
        (no SLO = best-effort, FIFO among best-effort peers). The
        SLO's ``priority`` tier also drives overload behavior: a full
        queue EVICTS the lowest-priority queued request (counted in
        ``evictions_total`` + its class's ``shed_*_total``) when the
        newcomer outranks it, instead of flat-shedding the newcomer.
        ``prefill_only=True``: the request resolves with a KV handoff
        blob (page contents + generated-so-far) instead of generated
        tokens — the disaggregated prefill replica's verb; feed the
        blob to a decode replica's :meth:`import_handoff`.
        ``queued_for_s``: seconds this request ALREADY waited upstream
        (a router redrive, a cross-process hop) — backdates
        ``enqueued_at`` so TTFT and the EDF deadline measure from the
        original arrival, never from the latest hop (an age, not an
        absolute timestamp, so it is clock-skew-free on the wire)."""
        if slo is not None and (
                not hasattr(slo, "ttft_target_s")
                or not hasattr(slo, "tpot_target_s")):
            raise TypeError(
                f"slo must be an SLOClass (serving.sched), got "
                f"{type(slo).__name__}")
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        max_new = (self.config.max_new_tokens if max_new is None
                   else int(max_new))
        seq = next(self._seq)
        with record_event("pt:engine/submit", req=seq,
                          prompt_len=prompt.size, max_new=max_new):
            return self._submit(seq, prompt, max_new, timeout, slo,
                                prefill_only, queued_for_s)

    def _submit(self, seq, prompt, max_new, timeout, slo, prefill_only,
                queued_for_s):
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size > self.config.prompt_buckets[-1]:
            self.metrics.incr("shed_total")
            raise BucketError(
                f"prompt of {prompt.size} tokens exceeds the largest "
                f"declared bucket {self.config.prompt_buckets[-1]}")
        if not 1 <= max_new <= self.config.max_new_tokens:
            raise ValueError(
                f"max_new must be in [1, {self.config.max_new_tokens}]"
                f", got {max_new}")
        rank = priority_rank(slo) if slo is not None \
            else PRIORITIES["standard"]
        if self.brownout is not None and rank == PRIORITIES["batch"] \
                and self.brownout.active("cap_batch_max_new") \
                and max_new > self._bo_max_new_cap:
            # brownout level >= 1: batch-tier generation is capped —
            # fewer tokens, identical numerics for every token served
            max_new = self._bo_max_new_cap
            self.metrics.incr("brownout_cap_max_new_total")
        if self._pages_needed(prompt.size, max_new) \
                > self.allocator.usable_pages:
            self.metrics.incr("shed_total")
            raise PagesExhaustedError(
                f"request needs {self._pages_needed(prompt.size, max_new)}"
                f" pages but the pool only has "
                f"{self.allocator.usable_pages} — grow n_pages or "
                "shorten the request")
        for kind, spec in self.kinds.items():
            if spec["pages_per_seq"] > self.allocator.usable_of(kind):
                self.metrics.incr("shed_total")
                raise PagesExhaustedError(
                    f"request needs {spec['pages_per_seq']} {kind} pages "
                    "but the pool only has "
                    f"{self.allocator.usable_of(kind)}")
        if not self.breaker.admits():
            self.metrics.incr("breaker_shed_total")
            raise ServiceUnavailableError(
                "circuit breaker open — the engine is failing; back "
                f"off at least {self.config.breaker_cooldown_s}s")
        if timeout is None:
            timeout = self.config.default_timeout_s
        now = time.monotonic()
        req = DecodeRequest(
            prompt=prompt, max_new=max_new,
            deadline=None if timeout is None else now + float(timeout),
            enqueued_at=now - max(0.0, float(queued_for_s)),
            slo=slo, prefill_only=prefill_only, seq=seq)
        victim = None
        with self._cv:
            if self._closed:
                raise ServerClosedError("decode engine is closed")
            if len(self._queue) >= self.config.max_queue:
                # priority eviction: displace the WORST queued request
                # iff the newcomer strictly outranks it — under
                # pressure batch leaves the queue first, interactive
                # never yields to anything
                worst_i = max(range(len(self._queue)),
                              key=lambda i: (
                                  priority_rank(self._queue[i]),
                                  self._queue[i].enqueued_at))
                if priority_rank(self._queue[worst_i]) > rank:
                    victim = self._queue.pop(worst_i)
                else:
                    self.metrics.incr("shed_total")
                    self.metrics.incr(
                        _SHED_BY_RANK.get(rank, "shed_standard_total"))
                    raise QueueFullError(
                        f"admission queue full "
                        f"({self.config.max_queue} requests) — load "
                        "shed, retry with backoff")
            self._queue.append(req)
            self._cv.notify_all()
        if victim is not None:
            self.metrics.incr("shed_total")
            self.metrics.incr("evictions_total")
            self.metrics.incr(
                _SHED_BY_RANK.get(priority_rank(victim),
                                  "shed_standard_total"))
            victim.set_error(QueueFullError(
                "evicted from a full admission queue by a "
                "higher-priority request — load shed, retry with "
                "backoff"))
        # progress mark for deterministic chaos barriers: "crash N loop
        # iterations after the K-th admission" (faultinject.arm after=)
        _faultinject.event("decode_submit")
        self.metrics.incr("requests_total")
        self.metrics.set_queue_depth(len(self._queue))
        return req

    def generate(self, prompt, max_new=None, timeout=None):
        """Synchronous convenience: submit + liveness-aware wait.
        Returns the generated tokens (1-D int64)."""
        req = self.submit(prompt, max_new=max_new, timeout=timeout)
        end = None if req.deadline is None else req.deadline + 10.0
        while True:
            if req.wait(0.05):
                return req.result(0)
            worker = self._worker
            if worker is None or not worker.is_alive():
                if req.wait(0.2):
                    return req.result(0)
                raise WorkerDiedError(
                    "decode worker died while this request waited "
                    "(restart the engine with start())")
            if end is not None and time.monotonic() >= end:
                return req.result(0)

    def import_handoff(self, state, timeout=None, slo=None):
        """Adopt a prefill replica's exported KV state: allocate local
        pages, copy the page contents in (an exact value copy — the
        paged cache is location-independent, so fresh page ids cost
        nothing), install a decode slot, and continue generating.
        Returns a DecodeRequest whose result is the FULL generated
        token sequence (handed-off tokens included). This is the
        decode half of the ``handoff`` replica verb.

        Typed rejections mirror submit(): ServingError on a malformed
        or geometry-mismatched blob, PagesExhaustedError when the
        state can never fit, QueueFullError / ServiceUnavailableError
        / ServerClosedError under load/failure."""
        if not isinstance(state, dict) \
                or state.get("kind") != "kv_handoff" \
                or not all(key in state for key in
                           ("prompt", "max_new", "pos", "cur", "prev",
                            "emitted", "pages", "page_size", "cache")):
            raise ServingError(
                "import_handoff needs the blob a prefill_only request "
                "resolved with (dict with kind='kv_handoff')")
        if int(state["page_size"]) != self.config.page_size:
            raise ServingError(
                f"handoff page_size {state['page_size']} != this "
                f"engine's {self.config.page_size} — prefill and "
                "decode replicas must share the page geometry")
        if set(state.get("kinds", {})) != set(self.kinds):
            raise ServingError(
                "handoff blob and this engine's model differ in their "
                f"cache kinds ({sorted(state.get('kinds', {}))} against "
                f"{sorted(self.kinds)} beside the sequence kind)")
        prompt = np.asarray(state["prompt"], np.int64).reshape(-1)
        max_new = int(state["max_new"])
        emitted = [int(t) for t in state["emitted"]]
        if timeout is None:
            timeout = self.config.default_timeout_s
        now = time.monotonic()
        req = DecodeRequest(
            prompt=prompt, max_new=max_new,
            deadline=None if timeout is None else now + float(timeout),
            enqueued_at=now, slo=slo, handoff_state=state,
            seq=next(self._seq))
        req.ttft_s = state.get("ttft_s")
        self.metrics.incr("requests_total")
        if state.get("done"):
            # the prefill side already finished the sequence (eos on
            # the first token / max_new == 1): settle without touching
            # the pool
            self.metrics.incr("handoff_import_total")
            self.metrics.incr("responses_total")
            self.metrics.incr("retired_total")
            req.set_result(np.asarray(emitted, dtype=np.int64))
            return req
        n_src = self._handoff_cache(state)[0].shape[1]
        if self.allocator.pages_for(prompt.size + max_new) \
                > self.allocator.usable_pages \
                or n_src > self.allocator.usable_pages:
            self.metrics.incr("shed_total")
            raise PagesExhaustedError(
                f"handoff state needs {n_src} pages but the pool "
                f"only has {self.allocator.usable_pages}")
        if not self.breaker.admits():
            self.metrics.incr("breaker_shed_total")
            raise ServiceUnavailableError(
                "circuit breaker open — handoff shed; back off at "
                f"least {self.config.breaker_cooldown_s}s")
        with self._cv:
            if self._closed:
                raise ServerClosedError("decode engine is closed")
            if len(self._queue) >= self.config.max_queue:
                self.metrics.incr("shed_total")
                raise QueueFullError(
                    f"admission queue full ({self.config.max_queue} "
                    "requests) — load shed, retry with backoff")
            self._queue.append(req)
            self._cv.notify_all()
        _faultinject.event("decode_submit")
        self.metrics.set_queue_depth(len(self._queue))
        return req

    def outstanding(self):
        """Admitted-but-unfinished requests: queued prompts plus
        active decode slots plus in-flight chunked prefills — the
        cluster router's balancing signal (cheap reads, not a
        stats() snapshot)."""
        with self._qlock:
            queued = len(self._queue)
        return (queued + sum(s is not None for s in self.slots)
                + len(self._chunk_jobs))

    def _simulate_worker_crash(self):
        """Kill THIS engine's worker ungracefully on its next loop
        iteration (per-engine SIGKILL model for cluster chaos).
        start() revives."""
        self._crash.set()

    def worker_alive(self):
        """True iff the worker thread exists and is running."""
        w = self._worker
        return w is not None and w.is_alive()

    def stats(self):
        snap = self.metrics.stats()
        snap["compiles_now"] = self.exe.total_compiles()
        # the time.monotonic() the build ended at: with
        # engine_build_s_total, where it lies beside the compile log
        snap["engine_built_at"] = self._built_at
        with self._qlock:
            snap["queue_depth"] = len(self._queue)
        snap["active_slots"] = sum(s is not None for s in self.slots)
        snap["active_chunk_jobs"] = len(self._chunk_jobs)
        snap["scheduler"] = getattr(self.sched, "name",
                                    type(self.sched).__name__)
        snap["max_batch"] = self.config.max_batch
        snap["pages_in_use"] = self.allocator.in_use
        snap["pages_available"] = self.allocator.available
        for kind, spec in self.kinds.items():
            unit = spec.get("unit", "pages")     # window_pages_in_use,
            snap[f"{kind}_{unit}_in_use"] = \
                self.allocator.in_use_of(kind)   # state_entries_in_use
            snap[f"{kind}_{unit}_available"] = \
                self.allocator.available_of(kind)
        snap["health_state"] = self.health.state
        snap["breaker"] = self.breaker.snapshot()
        snap["brownout"] = (None if self.brownout is None
                            else self.brownout.snapshot())
        snap["optimize"] = self.optimize_reports or None
        return snap

    # -- internal: program rewrites --------------------------------------
    def _bundles(self):
        """Every program bundle the engine dispatches, under the label
        its dispatch method gives ``_run_program``."""
        bundles = {f"prefill_{bucket}": b
                   for bucket, b in self.programs.prefill.items()}
        bundles.update(
            (f"draft_prefill_{bucket}", b)
            for bucket, b in (self.programs.draft_prefill or {}).items())
        bundles["decode"] = self.programs.decode
        if self.programs.chunk is not None:
            bundles["chunk"] = self.programs.chunk
        if self.programs.spec is not None:
            bundles["spec"] = self.programs.spec
        return bundles

    def _optimize_programs(self):
        """Runs the rewrite pipeline (Program.optimize) over every
        step-program bundle, keyed like the dispatch methods name
        them. All bundles are private clones built by
        build_llama_paged_programs, so in-place mutation leaks
        nowhere; fetch Variables are resolved by NAME because they
        belong to the pre-clone builder program."""
        import warnings
        for label, b in self._bundles().items():
            try:
                report = b["program"].optimize(
                    fetch_list=[v.name if hasattr(v, "name") else v
                                for v in b.get("probe", b)["fetch"]])
                if report:
                    self.optimize_reports[label] = report.to_dict()
            except Exception as e:  # pragma: no cover - safety net
                warnings.warn(
                    f"decode optimize rewrite failed on {label} "
                    f"({e!r}); serving it unoptimized", stacklevel=2)

    # -- internal: program dispatch --------------------------------------
    @staticmethod
    def _maybe_inject_fault():
        """serving_device_error fault point, raised INSIDE the retried
        dispatch so armed fault counts interact with the retry policy
        exactly as in ServingEngine."""
        if _faultinject.fires("serving_device_error"):
            from ..resilience.retry import TransientDeviceError
            raise TransientDeviceError(
                "injected serving-layer transient device error "
                "(UNAVAILABLE)")

    def _bundle_feed(self, bundle, arrays):
        return dict(zip(bundle["feeds"], arrays))

    def _zeroed_pools(self):
        """(pools, draft pools) of ``programs.pool_specs``, zeroed."""
        import jax.numpy as jnp
        return tuple(
            [jnp.zeros(tuple(shape), dtype) for shape, dtype in specs or ()]
            for specs in (self.programs.pool_specs,
                          self.programs.draft_pool_specs))

    def _replace_lost_pools(self):
        """The one rule for a consumed pool. Where any pool of this
        engine is deleted, the cache is lost: ALL pools become zeroed
        ones of ``programs.pool_specs``, every slot and chunk job that
        holds pages fails with ``PoolsLostError`` and frees them (no
        sequence continues on a zeroed cache), ``pools_lost_total``
        ticks, and the error is returned for the dispatch at hand to
        raise: it is not retryable. None where the pools are live, and
        where nothing held a page (the replacement is silent, but
        counted)."""
        if not any(p.is_deleted()
                   for p in (*self._pools, *self._draft_pools)):
            return None
        self._pools, self._draft_pools = self._zeroed_pools()
        self.metrics.incr("pools_lost_total")
        with self._slots_lock:
            held = [i for i, slot in enumerate(self.slots)
                    if slot is not None]
            jobs = sorted(self._chunk_jobs)
        if not held and not jobs:
            return None
        lost = PoolsLostError(
            "the cache pools were consumed by a dispatch that did not "
            "hand them back; every sequence that held pages lost its "
            "cache and is failed (the engine has zeroed pools again: "
            "submit the request anew)")
        self.metrics.incr("errors_total", len(held) + len(jobs))
        for i in held:
            self._retire(i, error=lost)
        for i in jobs:
            self._fail_chunk_job(i, lost)
        return lost

    # scope is passed explicitly to every run — scope_guard swaps a
    # process-global, which would race other live engines' threads
    def _run_program(self, label, b, arrays):
        """One dispatch of bundle ``b``: ``arrays`` then the pools it
        names (the target's; ``draft``: the draft's; ``both``: one after
        the other) in, DONATED (``Executor.run(donate_feeds=...)``): the
        program writes into the buffers it was fed, the arrays fed are
        deleted (``pools_consumed_total`` counts the dispatches where
        they were), and the pools are rebound to what comes back.
        Returns the token outputs as numpy; a ``stats`` fetch ticks the
        counters it names, any other extra stays on the device under
        ``kept[label]``. A dispatch that finds a pool deleted on entry,
        or fails once its pools are gone, goes by
        ``_replace_lost_pools``."""
        lost = self._replace_lost_pools()
        if lost is not None:
            raise lost
        try:
            return self._dispatch(label, b, arrays)
        except BaseException as exc:     # noqa: BLE001 — reraised
            lost = self._replace_lost_pools()
            if lost is None:
                raise
            raise lost from exc

    def _pools_of(self, b):
        """The pools bundle ``b`` takes in and hands back, in its order."""
        which = b.get("pools", "target")
        return ([] if which == "draft" else self._pools) \
            + ([] if which == "target" else self._draft_pools)

    def _dispatch(self, label, b, arrays):
        pools = self._pools_of(b)
        outs = self.exe.run(
            b["program"], feed=self._bundle_feed(b, (*arrays, *pools)),
            fetch_list=b["fetch"], mode="test", return_numpy=False,
            scope=self.scope, donate_feeds=b["feeds"][len(arrays):])
        consumed = all(p.is_deleted() for p in pools)
        extras = b.get("extras", ())
        n_head = len(outs) - len(pools) - len(extras)
        kept = dict(zip(extras, outs[n_head + len(pools):]))
        # what the host reads back, the tokens and the counters, sets out
        # for the host behind the program, not when the worker asks: a
        # fetch asked for afterwards is a blocking wait of its own, 0.5 ms
        # each in a process that wakes on time (PERF.md section 6, PR 31)
        for x in list(outs[:n_head]) + [kept[k] for k in kept
                                        if k == "stats"]:
            x.copy_to_host_async()
        # poll for the dispatch's end, do not block on it: a worker
        # blocked in the fetch is woken 2.5-3 ms late on the chip's
        # host in most processes (the "slow mode" of PERF.md section 2:
        # 9 of 14 runs of one cell, none of 6 when polling; PR 27). The
        # worker has nothing else to do until the tokens are there, and
        # sleep(0) hands the interpreter to whichever thread wants it.
        while not outs[0].is_ready():
            time.sleep(0)
        # the host copies land some 0.3 ms behind the program; to wait
        # for them inside np.asarray would be a blocking wait again
        landed = time.perf_counter() + _HOST_COPY_S
        while time.perf_counter() < landed:
            time.sleep(0)
        # the tokens first: a program that failed on the device raises
        # here, while the engine still holds the consumed pools it fed
        head = [np.asarray(x) for x in outs[:n_head]]
        back = list(outs[n_head:n_head + len(pools)])
        which = b.get("pools", "target")
        if which != "draft":
            self._pools, back = (back[:len(self._pools)],
                                 back[len(self._pools):])
        if which != "target":
            self._draft_pools = back
        if "stats" in kept:
            self.metrics.incr_many(dict(zip(
                self.programs.stats,
                (int(x) for x in np.asarray(kept.pop("stats"))))))
        if kept:
            self.kept[label] = kept
        else:                   # nor does an earlier dispatch's stay
            self.kept.pop(label, None)
        # ticked as the dispatch returns, beside its caller's own count:
        # a snapshot finds the two equal, not one dispatch apart
        if consumed:
            self.metrics.incr("pools_consumed_total")
        return head

    def _kind_tables(self, helds):
        """The rows' tables of every cache kind beyond ``sequence``, in
        ``programs.kinds``' order, each [rows, the kind's pages a request]:
        what a program takes behind the page table. ``helds``: a row's
        ``held`` mapping, or None for a row that holds nothing (the null
        page). () for a model with one kind, whose programs take one
        table."""
        out = []
        for kind, spec in self.kinds.items():
            t = np.zeros((len(helds), spec["pages_per_seq"]), np.int32)
            for i, held in enumerate(helds):
                if held is not None:
                    t[i] = held[kind]
            out.append(t)
        return tuple(out)

    def _ring_rows(self, rings):
        """The ``window`` kind's table alone, from the rows' rings (a
        model with that kind beside ``sequence`` and no other); None for a
        model without it."""
        if self.ring is None:
            return None
        return self._kind_tables([None if ring is None
                                  else {self.RING: ring}
                                  for ring in rings])[0]

    def _run_prefill_program(self, bucket, tokens, lens, table, *kinds):
        """The bucket's single-row program once for each row given, in
        order: the ``[rows]`` next tokens. ``kept`` holds what the last
        row's dispatch left. ``kinds``: the rows' tables of the model's
        further cache kinds (``_kind_tables``)."""
        return self._run_rows(f"prefill_{bucket}",
                              self.programs.prefill[bucket],
                              tokens, lens, table, *kinds)

    def _run_draft_prefill_program(self, bucket, tokens, lens, table):
        self._run_rows(f"draft_prefill_{bucket}",
                       self.programs.draft_prefill[bucket],
                       tokens, lens, table)

    def _run_rows(self, label, b, tokens, lens, table, *kinds):
        return np.concatenate([
            self._run_program(
                label, b, (tokens[i:i + 1], lens[i:i + 1], table[i:i + 1])
                + tuple(t[i:i + 1] for t in kinds))[0]
            for i in range(len(tokens))])

    def _run_chunk_program(self, tokens, lens, offsets, table, *kinds):
        return self._run_program(
            "chunk", self.programs.chunk,
            (tokens, lens, offsets, table) + kinds)[0]

    def _run_decode_program(self, tokens, positions, table, *kinds,
                            loop=False):
        """One decode dispatch: the ``[rows, decode_block]`` tokens.
        ``loop``: what the worker's loop and ``warmup`` pass, and get the
        bundle's serving form. Any other caller gets its ``probe`` form
        where it has one (a block-kind model's: the same Program with its
        whole fetch set, compiled at the first such call): ``kept["decode"]``
        then holds that dispatch's ``logits`` ``[rows, decode_block,
        vocab]`` float32 and ``picks``, on the device."""
        b = self.programs.decode
        if not loop and "probe" in b:
            b = {**b, **b["probe"]}
            self.metrics.incr("decode_probe_dispatches_total")
        return self._run_program(
            "decode", b, (tokens, positions, table) + kinds)[0]

    def _run_spec_program(self, tokens, prev, positions, table):
        emitted, accepted = self._run_program(
            "spec", self.programs.spec, (tokens, prev, positions, table))
        return emitted, accepted

    # -- internal: scheduler ---------------------------------------------
    def _pages_needed(self, prompt_len, max_new):
        c = self.config
        bucket = self._bucket_for(prompt_len)
        slack = c.decode_block + (c.gamma + 1 if self.draft_cfg else 0)
        return self.allocator.pages_for(
            max(bucket, prompt_len + max_new + slack))

    def _alloc(self, n_pages, grant=None):
        """What a request holds, cache kind -> pages: ``n_pages`` of the
        ``sequence`` kind and of every further kind the model has the
        kind's pages a request (a ring; a state entry): all of them or,
        with PagesExhaustedError, none. ``grant(kind, n)`` allocates
        (``allocator.alloc`` where None). Under ``_slots_lock``."""
        grant = grant or (lambda kind, n: self.allocator.alloc(n, kind))
        held = {}
        try:
            held[PageAllocator.SEQUENCE] = grant(PageAllocator.SEQUENCE,
                                                 n_pages)
            for kind, spec in self.kinds.items():
                held[kind] = grant(kind, spec["pages_per_seq"])
        except PagesExhaustedError:
            self._free(held)
            raise
        return held

    def _free(self, held):
        """A request's pages of every kind back. Under ``_slots_lock``."""
        for kind, pages in held.items():
            self.allocator.free(pages, kind)

    def _grows(self, r):
        """Whether request ``r`` takes its ``sequence`` pages as it writes
        them. Not where the engine cannot tell when its rows will ask: a
        speculative round advances rows unequally, a chunk job's decode
        starts an unknown number of dispatches later; and not a handoff
        import or a ``prefill_only`` request, whose pages travel whole.
        Those keep their whole reservation from admission on."""
        return (self.draft_cfg is None and r.handoff_state is None
                and not r.prefill_only and not self._is_chunk_path(r))

    def _grant(self, r, granted=(), grant=None):
        """What ``r`` is admitted with, or None where it has to wait
        (``_admit`` has the rule): ``(held, row)``, its pages by cache
        kind and its row ``(pos, left, total, grows)`` of the projection.
        Its pages of every kind must be free now, and the residents'
        projected peak, with ``r`` and the rows ``granted`` in this pass
        before it counted, inside the pool. ``grant``: as ``_alloc``'s."""
        total = self._pages_needed(r.prompt.size, r.max_new)
        if self._grows(r):
            first = min(total, self.allocator.pages_for(
                r.prompt.size + self.config.decode_block))
            row = (r.prompt.size, r.max_new - 1, total, True)
        else:
            first, row = total, (0, 0, total, False)
        # a prefill_only request's pages are exported and freed as its
        # first token lands, before any decode dispatch: no row at all
        transient = r.prefill_only and not self._is_chunk_path(r)
        with self._slots_lock:
            try:
                held = self._alloc(first, grant)
            except PagesExhaustedError:
                return None
            if not transient and not self._peak_fits([*granted, row]):
                self._free(held)
                self.metrics.incr("admit_projection_refusals_total")
                return None
        return held, row

    def _peak_fits(self, newcomers):
        """Whether the ``sequence`` pool holds the projected peak
        (``projected_peak``) of the residents, slots and chunk jobs, and
        ``newcomers``, rows ``(pos, left, total, grows)``. Not computed
        where everybody's whole reservation fits anyway: always, in a pool
        sized for ``max_batch`` longest requests. Under ``_slots_lock``."""
        usable = self.allocator.usable_pages
        if usable >= self.config.max_batch * self.pages_per_seq:
            return True
        seq = PageAllocator.SEQUENCE
        rows = list(newcomers)
        for slot in self.slots:
            if slot is not None:
                rows.append((slot.pos, slot.req.max_new - len(slot.emitted),
                             slot.grows_to or len(slot.held[seq]),
                             slot.grows_to is not None))
        rows += [(0, 0, len(job.held[seq]), False)
                 for job in self._chunk_jobs.values()]
        pos, left, total, grows = zip(*rows)
        return sum(total) <= usable or projected_peak(
            pos, left, total, grows, self.config.page_size,
            self.config.decode_block) <= usable

    def _grow(self, idx, slot):
        """Slot ``idx`` takes the pages its next decode dispatch writes and
        it does not hold yet: appended to what it holds, written into its
        table. False where the row sits the dispatch out: close() or the
        watchdog took it meanwhile, or the pool had no page, which the
        admission rule excludes (``page_stall_total``: a fault, not
        traffic)."""
        seq = slot.held[PageAllocator.SEQUENCE]
        short = min(slot.grows_to, self.allocator.pages_for(
            slot.pos + self.config.decode_block)) - len(seq)
        if short < 1:
            return True
        with self._slots_lock:
            if self.slots[idx] is not slot:
                return False
            try:
                pages = self.allocator.alloc(short)
            except PagesExhaustedError:
                self.metrics.incr("page_stall_total")
                return False
            slot.table[len(seq):len(seq) + short] = pages
            seq.extend(pages)
        self.metrics.incr("pages_grown_total", short)
        return True

    def _bucket_for(self, prompt_len):
        for b in self.config.prompt_buckets:
            if b >= prompt_len:
                return b
        raise BucketError(
            f"prompt length {prompt_len} exceeds the largest bucket")

    def _has_work(self):
        with self._qlock:
            queued = len(self._queue)
        return queued > 0 or any(s is not None for s in self.slots) \
            or bool(self._chunk_jobs)

    def _pressure(self):
        """The overload pressure signal in [0, 1]: max of (a) oldest
        queued wait normalized by the queue-delay target, (b) breaker
        open, (c) page-pool occupancy beyond 90% (full residency at
        steady state is normal; the last 10% means admission is about
        to wait on pages)."""
        now = time.monotonic()
        with self._qlock:
            oldest = min((r.enqueued_at for r in self._queue),
                         default=None)
        q = 0.0 if oldest is None else min(
            1.0, max(0.0, now - oldest) / self._bo_queue_target_s)
        b = 0.0 if self.breaker.admits() else 1.0
        in_use = self.allocator.in_use
        total = in_use + self.allocator.available
        occ = in_use / total if total else 0.0
        return max(q, b, max(0.0, (occ - 0.9) / 0.1))

    def _update_brownout(self):
        """One controller tick per worker iteration: feed the pressure
        signal, count level transitions. Returns True when the level
        moved (the loop treats that as progress so a braking engine
        keeps ticking)."""
        if self.brownout is None:
            return False
        old, new = self.brownout.update(self._pressure())
        if new > old:
            self.metrics.incr("brownout_engage_total")
        elif new < old:
            self.metrics.incr("brownout_revert_total")
        return new != old

    def _take_pending(self):
        """Remove and return every queued request plus every active
        slot's / chunk job's request, freeing their pages
        (shutdown/death path)."""
        with self._qlock:
            q, self._queue = self._queue, []
        pending = list(q)
        with self._slots_lock:
            for i, slot in enumerate(self.slots):
                if slot is not None:
                    pending.append(slot.req)
                    self._free(slot.held)
                    self.slots[i] = None
            jobs, self._chunk_jobs = dict(self._chunk_jobs), {}
            for job in jobs.values():
                pending.append(job.req)
                self._free(job.held)
        return pending

    def _sweep_expired(self):
        """Fail deadline-blown queued requests before any compute is
        spent on peers (the batching.py discipline)."""
        now = time.monotonic()
        expired = []
        with self._qlock:
            keep = []
            for r in self._queue:
                if r.deadline is not None and now >= r.deadline:
                    expired.append(r)
                else:
                    keep.append(r)
            self._queue = keep
        for r in expired:
            self.metrics.incr("timeouts_total")
            r.set_error(RequestTimeoutError(
                "request deadline expired before it was served "
                "(queue saturated or timeout too tight)"))
        return bool(expired)

    def _retire(self, idx, error=None, draining=False):
        with self._slots_lock:
            slot = self.slots[idx]
            if slot is None:      # already failed by close()/watchdog
                return
            self.slots[idx] = None
            self._free(slot.held)
        with record_event("pt:engine/retire", req=slot.req.seq,
                          tokens=len(slot.emitted)):
            self._settle(slot, error, draining)
        with self._cv:
            self._cv.notify_all()

    def _settle(self, slot, error, draining):
        """A retired slot's bookkeeping and its request's settlement,
        done-callbacks included (they run on this thread)."""
        now = time.monotonic()
        if error is not None:
            slot.req.set_error(error)
            return
        n = len(slot.emitted)
        if n > 1 and slot.first_token_at is not None:
            tpot = (now - slot.first_token_at) / (n - 1)
            self.metrics.observe_window("tpot_s", tpot)
            slo = slot.req.slo
            if slo is not None:
                if slo.tpot_target_s is not None:
                    self.metrics.incr(
                        "slo_tpot_met"
                        if tpot <= slo.tpot_target_s
                        else "slo_tpot_violated")
                self.metrics.observe_window(
                    f"{slo.name}.tpot_s", tpot)
        self.metrics.observe_latency(now - slot.req.enqueued_at)
        self.metrics.incr("responses_total")
        self.metrics.incr("retired_total")
        if draining:
            self.metrics.incr("drained_total")
        slot.req.set_result(
            np.asarray(slot.emitted, dtype=np.int64))

    def _is_chunk_path(self, r):
        """Long prompts go through the chunked-prefill path when the
        chunk program exists; handoff imports and short prompts never
        do."""
        return (r.handoff_state is None
                and self.programs.chunk is not None
                and r.prompt.size > self.programs.chunk_size)

    def _admit(self, policy):
        """Move queued prompts into free slots — in SCHEDULER order
        (serving/sched.py): each pass re-sorts the queue (EDF over
        TTFT deadlines for the SLO scheduler, arrival order for FIFO)
        and asks the scheduler whether prefill work may run this
        iteration at all (the TPOT budget guard defers admission to
        the decode batch when a running stream is about to blow its
        per-token budget). The head of the order then picks its path:
        handoff import (pages + an eager KV copy, no dispatch),
        chunked prefill (reserve a slot + pages now; the slices run in
        _step_chunks), or whole-prompt prefill: a group of up to
        ``prefill_batch`` same-bucket requests leaves the queue
        together and is dispatched REQUEST BY REQUEST, in the group's
        order, through the bucket's one single-row program
        (_prefill_request). Each first token is installed as its own
        dispatch returns, nothing waits to fill a dispatch, and a
        request runs the same executable alone or in company.

        **What a request holds, and who comes in** (``_grant``). A
        whole-prompt request takes its ``sequence`` pages AS IT WRITES
        THEM: at admission those its prefill writes and its first decode
        dispatch needs, before every later dispatch the difference
        (``_step``, ``_grow``); of every other kind, and where the engine
        cannot tell when a row will ask (``_grows``), the whole
        reservation of ``_pages_needed`` at once. The engine knows every
        resident's ``max_new`` and every decode dispatch advances every
        row ``decode_block`` positions, so the pool's use at every
        dispatch to come is known now (``projected_peak``). The queue's
        head comes in iff its admission pages are free now AND the
        residents' projected peak, with it counted, is at most the pool.
        THE INVARIANT: the projected peak is at most the pool after every
        admission, and a retirement, an early end (``eos_id``, a deadline,
        an error) or a close only lowers the sum at every dispatch to
        come; so every growth finds its page, no row ever stalls and none
        is preempted or recomputed. A growth that finds none is a fault
        of this rule, counted in ``page_stall_total``. A request refused
        either way goes back to the queue's front and waits for a
        retirement (``_page_wait``); a terminal prefill failure fails
        only that dispatch's request."""
        admitted = False
        self._page_bound = False
        while True:
            with self._slots_lock:
                free = [i for i, sl in enumerate(self.slots)
                        if sl is None and i not in self._chunk_jobs]
            if not free:
                break
            now = time.monotonic()
            with self._qlock:
                if not self._queue:
                    break
                self._queue = self.sched.order(self._queue, now)
                if not self.sched.admit_now(self._queue, self.slots,
                                            now):
                    break
                head = self._queue[0]
                if head.handoff_state is not None:
                    self._queue.pop(0)
                    plan = ("handoff", head)
                elif self._is_chunk_path(head):
                    self._queue.pop(0)
                    plan = ("chunk", head)
                else:
                    limit = min(len(free), self.config.prefill_batch)
                    bucket = self._bucket_for(head.prompt.size)
                    group, rest = [], []
                    for r in self._queue:
                        if (len(group) < limit
                                and r.handoff_state is None
                                and not self._is_chunk_path(r)
                                and self._bucket_for(r.prompt.size)
                                == bucket):
                            group.append(r)
                        else:
                            rest.append(r)
                    self._queue = rest
                    plan = ("prefill", bucket, group)
            if plan[0] == "handoff":
                if not self._admit_handoff(plan[1], free[0]):
                    break
                admitted = True
                continue
            if plan[0] == "chunk":
                if not self._start_chunk_job(plan[1], free[0]):
                    break
                admitted = True
                continue
            bucket, group = plan[1], plan[2]
            granted = []       # (req, held, row) actually prefilling now
            starved = []
            for r in group:
                got = None if starved else self._grant(
                    r, [row for _, _, row in granted])
                if got is None:
                    if not starved:
                        self._page_wait()
                    starved.append(r)
                    continue
                granted.append((r, *got))
            if starved:        # put them back at the front, in order
                with self._qlock:
                    self._queue[0:0] = starved
            if not granted:
                break
            self.metrics.set_queue_depth(len(self._queue))
            if not self.breaker.allow():
                with self._slots_lock:
                    for _, held, _ in granted:
                        self._free(held)
                self.metrics.incr("breaker_shed_total", len(granted))
                for r, _, _ in granted:
                    r.set_error(ServiceUnavailableError(
                        "circuit breaker open — prefill shed; back "
                        f"off {self.config.breaker_cooldown_s}s"))
                continue
            for (r, held, (_, _, total, grows)), idx in zip(granted, free):
                admitted |= self._prefill_request(
                    policy, bucket, r, held, idx,
                    grows_to=total if grows else None)
        return admitted

    def _page_wait(self):
        """The head of the queue found a free slot and not its pages: it
        goes back to the queue's front and waits for a retirement. Until
        the next admission pass the batch is bound by pages."""
        self.metrics.incr("page_wait_total")
        self._page_bound = True

    def _prefill_request(self, policy, bucket, r, held, idx,
                         grows_to=None):
        """One whole-prompt request's own dispatch of its bucket's
        single-row program (the draft's behind it), and its first token
        installed as that dispatch returns: True. A terminal failure
        frees the pages and fails this request alone: False. ``held``:
        the request's pages by cache kind, as ``_grant`` gave them: where
        the row grows (``grows_to``, ``_Slot``'s) they end short of the
        bucket, and the padding's entries land on the null page, where an
        inactive row's do."""
        pages = held[PageAllocator.SEQUENCE]
        kind_tables = self._kind_tables([held])
        tokens = np.zeros((1, bucket), np.int64)
        tokens[0, :r.prompt.size] = r.prompt
        lens = np.asarray([r.prompt.size], np.int32)
        table = np.zeros((1, self.pages_per_seq), np.int32)
        table[0, :len(pages)] = pages

        def _prefill_dispatch():
            self._maybe_inject_fault()
            nxt = self._run_prefill_program(bucket, tokens, lens, table,
                                            *kind_tables)
            if self.draft_cfg is not None:
                self._run_draft_prefill_program(bucket, tokens, lens,
                                                table)
            return nxt

        dispatch = record_event("pt:engine/prefill_dispatch",
                                bucket=bucket, rows=1, req=r.seq)
        admitted_at = time.monotonic()
        try:
            with dispatch:
                nxt = with_retries(
                    _prefill_dispatch, policy=policy,
                    deadline=r.deadline,
                    on_retry=lambda exc, n, delay:
                        self.metrics.incr("retries_total"))
        except BaseException as exc:     # noqa: BLE001 — forwarded
            self._tick(prefill_dispatch_s_total=dispatch.seconds)
            with self._slots_lock:
                self._free(held)
            if self.breaker.record_failure():
                self.metrics.incr("breaker_open_total")
                self.health.to(HealthState.DEGRADED)
            self.metrics.incr("errors_total")
            r.set_error(exc)
            return False
        self.breaker.record_success()
        self._tick(prefill_dispatch_total=1,
                   prefill_attn_in_kernel_total=int(
                       self.programs.prefill[bucket].get(
                           "attn_in_kernel", False)),
                   prefill_experts_in_kernel_total=int(
                       self.programs.prefill[bucket].get(
                           "experts_in_kernel", False)),
                   prefill_dispatch_s_total=dispatch.seconds,
                   prefill_tokens_total=int(r.prompt.size),
                   prefill_padded_tokens_total=bucket,
                   state_resets_total=int(self.STATE in held),
                   queue_wait_s_total=admitted_at - r.enqueued_at)
        self._install_first_token(r, held, table[0], int(nxt[0]), idx,
                                  grows_to)
        return True

    def _score_ttft(self, r):
        """SLO attainment bookkeeping for a freshly prefilled request:
        met/violated counter (only when the class has a TTFT half) and
        the per-class latency window."""
        slo = r.slo
        if slo is None or r.ttft_s is None:
            return
        if slo.ttft_target_s is not None:
            self.metrics.incr("slo_ttft_met"
                              if r.ttft_s <= slo.ttft_target_s
                              else "slo_ttft_violated")
        self.metrics.observe_window(f"{slo.name}.ttft_s", r.ttft_s)

    def _install_first_token(self, r, held, table, first, idx,
                             grows_to=None):
        """Post-prefill bookkeeping shared by whole-prompt admission
        and the final chunk of a chunked prefill: TTFT accounting,
        then either a decode slot install or — for ``prefill_only``
        requests — a KV handoff export (the request resolves with the
        handoff blob instead of occupying a slot). ``grows_to``: the
        slot's (``_Slot``)."""
        now = time.monotonic()
        r.ttft_s = now - r.enqueued_at
        self.metrics.observe_window("ttft_s", r.ttft_s)
        self._score_ttft(r)
        self.metrics.incr("prefill_total")
        self.metrics.incr("generated_tokens_total")
        if self.RING in held:
            self._count_recycled(0, r.prompt.size)
        if r.prefill_only:
            self._export_handoff(r, held, first)
            return
        with self._slots_lock:
            self.slots[idx] = _Slot(
                r, held, table, pos=r.prompt.size, cur=first,
                prev=int(r.prompt[-1]), emitted=[first],
                first_token_at=now, grows_to=grows_to)
        eos = self.config.eos_id
        if (eos is not None and first == eos) or r.max_new == 1:
            self._retire(idx, draining=self._closed
                         and not self._stop.is_set())

    def _export_handoff(self, r, held, first):
        """Resolve a ``prefill_only`` request with the KV handoff
        blob: the filled page CONTENTS in table order (sequence
        position p lives at blob page ``p // page_size``; in a pool of
        the ``window`` kind at the ring's page ``(p // page_size) % ring
        pages``; a pool of the ``state`` kind gives the request's one
        entry), the exporter's pages of each further kind under ``kinds``
        in the blob, the prompt, and the tokens generated so far. Pages
        are freed here — the
        blob owns the KV state now; import allocates fresh pages on
        the destination, so the handoff is location-independent."""
        with self._slots_lock:
            exported = {kind: self.allocator.export_state(pages, kind)
                        for kind, pages in held.items()}
        alloc_state = exported.pop(PageAllocator.SEQUENCE)
        cache = [np.asarray(pool)[:, np.asarray(held[kind], np.int64)]
                 for kind, pool in zip(self._pool_kind, self._pools)]
        with self._slots_lock:
            self._free(held)
        eos = self.config.eos_id
        done = (eos is not None and first == eos) or r.max_new == 1
        if done:
            # a finished request needs no KV — the importer resolves it
            # without a decode slot, so don't ship dead pages
            cache = [x[:, :0] for x in cache]
            alloc_state = {"pages": [], "page_size":
                           alloc_state["page_size"]}
        state = {"kind": "kv_handoff",
                 "prompt": np.asarray(r.prompt, np.int64),
                 "max_new": int(r.max_new),
                 "pos": int(r.prompt.size),
                 "cur": int(first),
                 "prev": int(r.prompt[-1]),
                 "emitted": [int(first)],
                 "pages": alloc_state["pages"],
                 "page_size": alloc_state["page_size"],
                 "cache": cache,
                 "done": bool(done),
                 "ttft_s": r.ttft_s}
        if exported:
            state["kinds"] = {kind: [] if done else st["pages"]
                              for kind, st in exported.items()}
        self.metrics.incr("handoff_export_total")
        self.metrics.observe_latency(time.monotonic() - r.enqueued_at)
        self.metrics.incr("responses_total")
        self.metrics.incr("retired_total")
        r.set_result(state)
        with self._cv:
            self._cv.notify_all()

    def _handoff_cache(self, state):
        """The page contents a handoff blob carries, one array a pool of
        this engine's model, checked against the pools' entry shapes (a
        blob of another model's cache would scatter nonsense)."""
        cache = [np.asarray(x) for x in state["cache"]]
        want = [tuple(p.shape[2:]) for p in self._pools]
        got = [tuple(x.shape[2:]) for x in cache]
        if got != want:
            raise ServingError(
                f"handoff cache entries {got} do not match this "
                f"engine's pools {want}")
        return cache

    def _admit_handoff(self, r, idx):
        """Install an imported handoff blob into slot ``idx``: fresh
        pages, an exact value copy of the exported page contents into
        the local pools (an EAGER array update — no program dispatch,
        no new executable, so the no-recompile pin is untouched), and
        a decode slot resuming at the handed-off position. Returns
        False (request requeued at the front) on page exhaustion."""
        state = r.handoff_state
        cache = self._handoff_cache(state)
        n_src = int(cache[0].shape[1])
        theirs = state.get("kinds", {})

        def grant(kind, n):
            pages = state["pages"] if kind == PageAllocator.SEQUENCE \
                else theirs[kind]
            return self.allocator.import_alloc(
                {"pages": pages, "page_size": state["page_size"]},
                total=n, kind=kind)

        got = self._grant(r, grant=grant)
        if got is None:
            self._page_wait()
            with self._qlock:
                self._queue.insert(0, r)
            return False
        held = got[0]
        import jax.numpy as jnp
        pages = held[PageAllocator.SEQUENCE]
        rows = {kind: np.asarray(
            got[:n_src] if kind == PageAllocator.SEQUENCE else got,
            np.int64) for kind, got in held.items()}
        self._pools = [
            pool.at[:, rows[kind]].set(jnp.asarray(x, pool.dtype))
            for kind, pool, x in zip(self._pool_kind, self._pools, cache)]
        table = np.zeros((self.pages_per_seq,), np.int32)
        table[:len(pages)] = pages
        emitted = [int(t) for t in state["emitted"]]
        with self._slots_lock:
            self.slots[idx] = _Slot(
                r, held, table, pos=int(state["pos"]),
                cur=int(state["cur"]), prev=int(state["prev"]),
                emitted=emitted, first_token_at=time.monotonic())
        self.metrics.incr("handoff_import_total")
        eos = self.config.eos_id
        if (eos is not None and emitted and emitted[-1] == eos) \
                or len(emitted) >= r.max_new:
            self._retire(idx, draining=self._closed
                         and not self._stop.is_set())
        return True

    def _start_chunk_job(self, r, idx):
        """Reserve slot ``idx`` and the request's full page budget for
        a chunked prefill. No dispatch happens here — the slices run
        one per engine iteration in _step_chunks, interleaved with the
        decode batch. Returns False (request requeued at the front) on
        page exhaustion."""
        got = self._grant(r)
        if got is None:
            self._page_wait()
            with self._qlock:
                self._queue.insert(0, r)
            return False
        held = got[0]
        pages = held[PageAllocator.SEQUENCE]
        table = np.zeros((self.pages_per_seq,), np.int32)
        table[:len(pages)] = pages
        with self._slots_lock:
            self._chunk_jobs[idx] = _ChunkJob(r, held, table)
        self._tick(queue_wait_s_total=time.monotonic() - r.enqueued_at)
        return True

    def _fail_chunk_job(self, idx, exc):
        """Fail the chunk job at ``idx`` and free its pages: False where
        close(), the watchdog or a lost pool settled it already."""
        with self._slots_lock:
            job = self._chunk_jobs.pop(idx, None)
            if job is None:
                return False
            self._free(job.held)
        job.req.set_error(exc)
        with self._cv:
            self._cv.notify_all()
        return True

    def _step_chunks(self, policy):
        """One chunk dispatch per in-flight chunked prefill — chunk
        work is per-step work, interleaved with the decode batch so a
        long prompt never monopolizes the worker between decode steps.
        The final chunk's NextTok is the request's first token (TTFT
        lands there, via _install_first_token). A terminal dispatch
        failure fails only that job's request."""
        with self._slots_lock:
            jobs = sorted(self._chunk_jobs)
        if not jobs:
            return False
        if len(jobs) > 1 and self.brownout is not None \
                and self.brownout.active("chunk_shrink"):
            # brownout level 3: one chunk slice per iteration — decode
            # steps for running streams outrank prefill progress for
            # queued long prompts while the crowd passes
            self.metrics.incr("brownout_chunk_defer_total",
                              len(jobs) - 1)
            jobs = jobs[:1]
        cs = self.programs.chunk_size
        progressed = False
        for idx in jobs:
            with self._slots_lock:
                job = self._chunk_jobs.get(idx)
            if job is None:
                continue
            r = job.req
            if r.deadline is not None \
                    and time.monotonic() >= r.deadline:
                self.metrics.incr("timeouts_total")
                self._fail_chunk_job(idx, RequestTimeoutError(
                    "request deadline expired mid-chunked-prefill"))
                progressed = True
                continue
            sl = r.prompt[job.off:job.off + cs]
            tokens = np.zeros((1, cs), np.int64)
            tokens[0, :sl.size] = sl
            lens = np.asarray([sl.size], np.int32)
            offs = np.asarray([job.off], np.int32)
            table = job.table.reshape(1, -1)
            kind_tables = self._kind_tables([job.held])

            def _chunk_dispatch():
                self._maybe_inject_fault()
                return self._run_chunk_program(tokens, lens, offs,
                                               table, *kind_tables)

            dispatch = record_event("pt:engine/chunk_dispatch",
                                    req=r.seq, offset=job.off)
            try:
                with dispatch:
                    nxt = with_retries(
                        _chunk_dispatch, policy=policy,
                        deadline=r.deadline,
                        on_retry=lambda exc, n, delay:
                            self.metrics.incr("retries_total"))
            except BaseException as exc:  # noqa: BLE001 — forwarded
                self._tick(chunk_dispatch_s_total=dispatch.seconds)
                if self.breaker.record_failure():
                    self.metrics.incr("breaker_open_total")
                    self.health.to(HealthState.DEGRADED)
                if self._fail_chunk_job(idx, exc):
                    self.metrics.incr("errors_total")
                progressed = True
                continue
            self.breaker.record_success()
            self._tick(chunk_prefill_total=1,
                       prefill_attn_in_kernel_total=int(
                           self.programs.chunk.get("attn_in_kernel",
                                                   False)),
                       prefill_experts_in_kernel_total=int(
                           self.programs.chunk.get("experts_in_kernel",
                                                   False)),
                       chunk_dispatch_s_total=dispatch.seconds,
                       prefill_tokens_total=int(sl.size),
                       prefill_padded_tokens_total=cs,
                       state_resets_total=int(self.STATE in job.held
                                              and job.off == 0))
            job.off += int(sl.size)
            progressed = True
            if job.off >= r.prompt.size:
                # close() or the watchdog may have taken the job (and
                # freed its pages) while this slice ran: install only a
                # job that is still this engine's
                with self._slots_lock:
                    live = self._chunk_jobs.pop(idx, None) is job
                if live:
                    self._install_first_token(r, job.held, job.table,
                                              int(nxt[0]), idx)
        return progressed

    def _active(self):
        return [(i, s) for i, s in enumerate(self.slots)
                if s is not None]

    def _step(self, policy):
        """One decode (or speculative) dispatch over the full slot
        array; per-row bookkeeping afterwards. A terminal dispatch
        failure fails every active request (and trips the breaker),
        never the worker."""
        active = self._active()
        if not active:
            return False
        now = time.monotonic()
        for i, slot in list(active):
            if slot.req.deadline is not None \
                    and now >= slot.req.deadline:
                self.metrics.incr("timeouts_total")
                self._retire(i, error=RequestTimeoutError(
                    "request deadline expired mid-generation"))
        active = self._active()
        if not active:
            return True
        c = self.config
        # a row that takes its pages as it writes them is granted, before
        # the dispatch, what it writes in it (_grow)
        seq = PageAllocator.SEQUENCE
        sits_out = [i for i, slot in active if slot.grows_to is not None
                    and slot.pos + c.decode_block
                    > len(slot.held[seq]) * c.page_size
                    and not self._grow(i, slot)]
        if sits_out:
            active = [(i, slot) for i, slot in active if i not in sits_out]
            if not active:
                return False
        B = c.max_batch
        toks = np.zeros((B,), np.int64)
        prev = np.zeros((B,), np.int64)
        pos = np.ones((B,), np.int32)
        table = np.zeros((B, self.pages_per_seq), np.int32)
        helds = [None] * B
        for i, slot in active:
            toks[i] = slot.cur
            prev[i] = slot.prev
            pos[i] = slot.pos
            table[i] = slot.table
            helds[i] = slot.held
        kind_tables = self._kind_tables(helds)
        deadlines = [s.req.deadline for _, s in active
                     if s.req.deadline is not None]
        batch_deadline = min(deadlines) if deadlines else None
        # brownout level >= 2 runs the (warmed) plain decode program
        # instead of the spec step: exact greedy output either way —
        # verification pins spec to target-greedy parity — so the
        # switch trades draft speedup for target-model load, never
        # numerics. Stale draft KV across the gap only lowers
        # acceptance after revert; it cannot change tokens.
        use_spec = self.draft_cfg is not None
        if use_spec and self.brownout is not None \
                and self.brownout.active("spec_off"):
            use_spec = False
            self.metrics.incr("brownout_spec_off_total")

        def _step_dispatch():
            self._maybe_inject_fault()
            if not use_spec:
                return self._run_decode_program(toks, pos, table,
                                                *kind_tables, loop=True)
            return self._run_spec_program(toks, prev, pos, table)

        dispatch = record_event("pt:engine/decode_dispatch",
                                rows=len(active), spec=int(use_spec))
        try:
            with dispatch:
                result = with_retries(
                    _step_dispatch, policy=policy,
                    deadline=batch_deadline,
                    on_retry=lambda exc, n, delay:
                        self.metrics.incr("retries_total"))
            if not use_spec:
                out = result
            else:
                emitted, accepted = result
        except BaseException as exc:     # noqa: BLE001 — forwarded
            self._tick(decode_dispatch_s_total=dispatch.seconds)
            if self.breaker.record_failure():
                self.metrics.incr("breaker_open_total")
                self.health.to(HealthState.DEGRADED)
            # slots a lost pool took are settled and counted already
            active = self._active()
            self.metrics.incr("errors_total", len(active))
            for i, _ in active:
                self._retire(i, error=exc)
            return True
        self.breaker.record_success()
        if self.health.state == HealthState.DEGRADED:
            self.health.to(HealthState.READY)
        # what the active slots held through this dispatch: pages of
        # every kind, whole, and the positions resident in them
        self._tick(decode_batches_total=1,
                   decode_page_bound_total=int(self._page_bound),
                   decode_in_place_total=int(
                       not use_spec
                       and self.programs.decode.get("in_place", False)),
                   state_step_in_kernel_total=int(
                       not use_spec and self.programs.decode.get(
                           "state_in_kernel", False)),
                   decode_experts_in_kernel_total=int(
                       not use_spec and self.programs.decode.get(
                           "experts_in_kernel", False)),
                   decode_dispatch_s_total=dispatch.seconds,
                   cache_bytes_held_total=sum(
                       self._held_bytes(s) for _, s in active),
                   state_bytes_held_total=sum(
                       self._held_bytes(s, self.STATE) for _, s in active),
                   cache_positions_resident_total=sum(
                       s.pos for _, s in active))
        if self.RING in self.kinds:
            for _, slot in active:
                self._count_recycled(slot.pos, slot.pos + c.decode_block)
        draining = self._closed and not self._stop.is_set()
        eos = c.eos_id
        n_new = 0
        if not use_spec:
            for i, slot in active:
                row = out[i]
                taken, done = self._truncate(slot, row)
                slot.emitted.extend(taken)
                n_new += len(taken)
                slot.pos += len(row)
                slot.cur = int(row[-1])
                slot.prev = int(row[-2]) if len(row) >= 2 \
                    else int(toks[i])
                if done:
                    self._retire(i, draining=draining)
        else:
            self.metrics.incr("spec_rounds_total", len(active))
            for i, slot in active:
                a = int(accepted[i])
                row = emitted[i]
                self.metrics.incr("spec_tokens_accepted_total", a)
                taken, done = self._truncate(slot, row[:a])
                slot.emitted.extend(taken)
                n_new += len(taken)
                old_cur = slot.cur
                slot.pos += a
                slot.cur = int(row[a - 1])
                slot.prev = int(row[a - 2]) if a >= 2 else old_cur
                if done:
                    self._retire(i, draining=draining)
        self.metrics.incr("generated_tokens_total", n_new)
        return True

    def _held_bytes(self, slot, only=None):
        """Bytes of cache a slot's pages hold, pages whole: of every kind,
        or of the kind ``only``."""
        return sum(len(pages) * self._page_bytes[kind]
                   for kind, pages in slot.held.items()
                   if only is None or kind == only)

    def _count_recycled(self, pos0, pos1):
        """A request's positions ``pos0 .. pos1 - 1`` were written: count
        the ring pages that were entered anew and had held an earlier
        page's worth of positions (``window_pages_recycled_total``)."""
        ps = self.config.page_size
        n = self.kinds[self.RING]["pages_per_seq"]
        first, last = -(-pos0 // ps), (pos1 - 1) // ps  # entered anew
        turns = max(0, last - max(first, n) + 1)
        if turns:
            self.metrics.incr("window_pages_recycled_total", turns)

    def _truncate(self, slot, row):
        """The slice of freshly generated ``row`` this slot actually
        keeps: cut at eos_id (inclusive) and at the request's max_new.
        Returns (tokens, done)."""
        eos = self.config.eos_id
        row = [int(t) for t in row]
        if eos is not None and eos in row:
            row = row[:row.index(eos) + 1]
        room = slot.req.max_new - len(slot.emitted)
        done = (len(row) >= room
                or (eos is not None and row and row[-1] == eos))
        return row[:room], done

    # -- worker / watchdog -----------------------------------------------
    def _tick(self, clock="loop_busy_s_total", **deltas):
        """Worker thread only. Credits the time since the last tick to
        ``clock`` and adds ``deltas``, under one lock: a snapshot then
        holds a dispatch's count, its seconds and the busy time up to
        its end together, and busy + idle is the worker's life with
        nothing left out."""
        now = time.perf_counter()
        deltas[clock] = now - self._clock
        self._clock = now
        self.metrics.incr_many(deltas)

    def _worker_loop(self):
        policy = self.config.retry_policy or default_policy()
        self._clock = time.perf_counter()
        while not self._stop.is_set():
            # the crash point is consumed only while this engine has
            # work: fires() advances a process-global clock, so an IDLE
            # engine polling the point (a drained fixture, a spare pool
            # replica) would otherwise steal a fire armed against the
            # loaded engine under test
            if self._crash.is_set() or (
                    self._has_work()
                    and _faultinject.fires("serving_worker_crash")):
                return   # models SIGKILL — the watchdog's job
            with record_event("pt:engine/loop"):
                self.health.beat()
                moved = self._update_brownout()
                swept = self._sweep_expired()
                with record_event(
                        "pt:engine/admit", queued=len(self._queue),
                        free=sum(s is None for s in self.slots)):
                    admitted = self._admit(policy)
                chunked = self._step_chunks(policy)
                with record_event(
                        "pt:engine/step",
                        rows=sum(s is not None for s in self.slots)):
                    stepped = self._step(policy)
            self._tick()
            if self._closed and not self._has_work():
                break    # drain complete
            if not (admitted or chunked or stepped or swept or moved):
                with self._cv:
                    if not self._queue and not self._closed:
                        with record_event("pt:engine/idle"):
                            self._cv.wait(0.02)
                        self._tick("loop_idle_s_total")
        self._tick()
        for req in self._take_pending():
            req.set_error(ServerClosedError("engine closed"))

    def _watchdog_loop(self):
        while not self._watchdog_stop.wait(
                self.config.watchdog_interval_s):
            if self._stop.is_set() or self._closed:
                continue
            worker = self._worker
            if worker is None:
                continue
            if not worker.is_alive():
                self._on_worker_dead("decode worker thread died")
                continue
            age = self.health.heartbeat_age()
            hang = self.config.hang_timeout_s
            if hang and age is not None and age > hang:
                self._on_worker_dead(
                    f"decode worker heartbeat stalled {age:.1f}s "
                    f"(hang timeout {hang:g}s) — worker is stuck")

    def _on_worker_dead(self, reason):
        if not self._worker_death_seen:
            self._worker_death_seen = True
            self.metrics.incr("worker_died_total")
            self.health.to(HealthState.DEGRADED)
        for req in self._take_pending():
            req.set_error(WorkerDiedError(reason))
