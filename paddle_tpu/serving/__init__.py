"""paddle_tpu.serving — inference serving: dynamic micro-batching over
pre-compiled shape buckets, admission control, serving metrics, and
continuous batching for LLM decode.

The one-executable-per-program design (ARCHITECTURE.md) makes serving
a shape-discipline problem: XLA wants every shape pinned, traffic
arrives one request at a time. This package closes that gap —
``BucketSpec`` declares the padded shapes, ``ServingEngine`` coalesces
concurrent requests into bucket-shaped micro-batches under a deadline,
warms every bucket at load, sheds at capacity, and reports itself via
``stats()``. Failure is a defined state, not an accident (health.py):
a health state machine + hang watchdog, engine- and per-bucket circuit
breakers, graceful drain (``close(drain=True)``), and deadline
propagation into dispatch retries. See docs/SERVING.md.

Autoregressive decode gets its own engine (decode_engine.py):
``DecodeEngine`` schedules at iteration level over a paged KV cache
(kv_pages.py) — requests join and leave the fixed-shape decode batch
every step, the executable compiles once per (model, max_batch) and
never again, and speculative decoding is an engine mode. See
docs/SERVING.md "Continuous decode batching".

    from paddle_tpu import serving
    eng = serving.ServingEngine.from_saved_model("./model_dir",
              buckets=serving.BucketSpec(batch_sizes=(1, 4, 8)))
    eng.warmup()
    out = eng.infer({"img": x})          # x: [1, ...] single sample
"""
from .batching import (MicroBatcher, PendingResult, QueueFullError,  # noqa: F401
                       RequestTimeoutError, ServerClosedError,
                       ServingError)
from .buckets import BucketError, BucketSpec                         # noqa: F401
from .decode_engine import (DecodeConfig, DecodeEngine,              # noqa: F401
                            DecodeRequest, PoolsLostError)
from .engine import ServingConfig, ServingEngine                     # noqa: F401
from .health import (CircuitBreaker, HealthMonitor, HealthState,     # noqa: F401
                     ServiceUnavailableError, WorkerDiedError)
from .kv_pages import PageAllocator, PagesExhaustedError             # noqa: F401
from .metrics import ServingMetrics                                  # noqa: F401
from .overload import (AdmissionController, BrownoutController,      # noqa: F401
                       RetryBudget, RetryBudgetExhaustedError)
from .sched import (PRIORITIES, FIFOScheduler, SLOClass,             # noqa: F401
                    SLOScheduler, get_scheduler, priority_rank)

__all__ = ["AdmissionController", "BrownoutController", "BucketError",
           "BucketSpec", "CircuitBreaker", "DecodeConfig",
           "DecodeEngine", "DecodeRequest", "FIFOScheduler",
           "HealthMonitor", "HealthState", "MicroBatcher",
           "PRIORITIES", "PageAllocator", "PagesExhaustedError",
           "PendingResult", "PoolsLostError", "QueueFullError",
           "RequestTimeoutError",
           "RetryBudget", "RetryBudgetExhaustedError", "SLOClass",
           "SLOScheduler", "ServerClosedError",
           "ServiceUnavailableError", "ServingError", "ServingConfig",
           "ServingEngine", "ServingMetrics", "WorkerDiedError",
           "get_scheduler", "priority_rank"]
