"""The serving cells: a DecodeEngine built from a configuration file,
driven by a traffic file through loadgen.

set_up() is set-up (weights made on the device from the seed in one jitted
call, the engine's own warm-up of its two prefill buckets and the decode
step). measure() is one request alone (the replay check, and the last of
the warm-up), the lead-in traffic, the window, and for an open loop the
untimed drain.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from .. import loadgen
from ..metrics import _requests
from ..tracing import TRACE_SECONDS, span

# counters that must not move inside the window for a run to be correct
MUST_BE_ZERO = ("retries_total", "breaker_open_total", "worker_died_total",
                "errors_total", "timeouts_total", "shed_total")


def llama_config(model):
    """LlamaConfig from the published config.json keys in the file."""
    if model["hidden_size"] // model["num_attention_heads"] \
            != model["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_hidden=model["intermediate_size"],
        rope_base=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model["torch_dtype"])


def make_generator_weights(cfg, seed, quantize):
    """Every tensor of the generator layout (models/llama.py), made on the
    default device in one jitted call from the seed, in the type it is
    served in. int8: uniform in [-100, 100] with the constant scale
    1.6e-4 that 0.02-sized weights quantize to, as
    random_int8_generator_weights has it; float: normal(0, 0.02)."""
    hd = cfg.dim // cfg.n_heads
    L, D, V, F = cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.ffn_hidden
    mats = {"blocks.wq": (L, D, cfg.n_heads * hd),
            "blocks.wk": (L, D, cfg.n_kv_heads * hd),
            "blocks.wv": (L, D, cfg.n_kv_heads * hd),
            "blocks.wo": (L, cfg.n_heads * hd, D),
            "blocks.w_gate": (L, D, F), "blocks.w_up": (L, D, F),
            "blocks.w_down": (L, F, D), "lm_head": (D, V)}
    dt = jnp.dtype(cfg.dtype)

    def make(key):
        out = {}
        keys = jax.random.split(key, len(mats) + 1)
        for k, (name, shape) in zip(keys, sorted(mats.items())):
            if quantize:
                out[name] = jax.random.randint(
                    k, shape, -100, 101, jnp.int8)
                out[name + "@scale"] = jnp.full(
                    (V,) if name == "lm_head" else (L, 1, shape[-1]),
                    1.6e-4, jnp.float32)
            else:
                out[name] = (0.02 * jax.random.normal(k, shape)).astype(dt)
        out["tok_emb"] = (0.02 * jax.random.normal(
            keys[-1], (V, D))).astype(dt)
        out["blocks.attn_norm"] = jnp.ones((L, D), dt)
        out["blocks.mlp_norm"] = jnp.ones((L, D), dt)
        out["final_norm"] = jnp.ones((D,), dt)
        return out

    return jax.jit(make)(seed_key(seed))


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x3FFFFFFF),
                              seed >> 30)


class ServeSystem:
    def __init__(self, config, seed):
        self.config = config
        b = config["builder"]
        self.cfg = llama_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            weights = make_generator_weights(
                self.cfg, seed, bool(b["engine"].get("quantize")))
            for name, value in weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = DecodeEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**b["engine"]))
            self.warmup = self.engine.warmup()
        print(f"serve: engine up, warm-up {self.warmup}, pool "
              f"{self.engine.allocator.usable_pages} pages", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeSystem(config, seed)


def _counters(engine):
    s = engine.stats()
    keep = {k: v for k, v in s.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
    keep["compiles"] = s["compiles_now"]
    return keep


def _host_clocks():
    """What the host spent, read at the window's two edges and printed as
    differences: this process's CPU seconds, the engine worker thread's
    own, and the ticks the hypervisor gave another guest (``steal`` in
    /proc/stat). They tell a slow host (more CPU seconds for the same
    dispatches, or stolen ticks) from a slow device (the same CPU
    seconds, more waiting)."""
    out = {"process_cpu_s": time.process_time()}
    for th in threading.enumerate():
        if th.name == "paddle-tpu-decode-worker":
            out["engine_thread_cpu_s"] = time.clock_gettime(
                time.pthread_getcpuclockid(th.ident))
    try:
        with open("/proc/stat") as f:
            out["steal_ticks"] = float(f.readline().split()[8])
    except (OSError, IndexError, ValueError):
        pass
    return out


def measure(system, traffic, seconds, seed, tracer):
    """Runs the cell's traffic once. Returns the run's records: per-request
    times, counter snapshots at the window's two edges, what was offered,
    how late the generator ran, and the correctness findings."""
    engine = system.engine
    reqs = loadgen.make_requests(traffic, seconds, seed,
                                 system.cfg.vocab_size)
    offered = loadgen.length_summary(reqs)
    print("offered:", {p: {k: v for k, v in d.items()
                           if not k.endswith("_lens")}
                       for p, d in offered.items()}, flush=True)

    # one request alone, before any other: the shortest answer of the
    # measured list, later met again inside the mix
    if traffic["loop"] == "open":
        mid = [i for i, r in enumerate(reqs) if r["phase"] == "window"
               and 0.25 * seconds <= r["due_s"] < 0.75 * seconds]
    else:       # the second and third round of the clients' list
        n = traffic["clients"]
        mid = list(range(n, min(3 * n, len(reqs))))
    probe = min(mid, key=lambda i: (reqs[i]["max_new"],
                                    reqs[i]["prompt"].size))
    with span("probe_alone"):
        alone = np.asarray(engine.generate(
            reqs[probe]["prompt"], max_new=reqs[probe]["max_new"]))

    edges = {}

    def read_tokens():
        return engine.metrics.stats()["generated_tokens_total"]

    def edge(which):
        t, _ = loadgen.snap_to_tick(read_tokens)
        snap = _counters(engine)
        snap.update(t=t, **_host_clocks())
        edges[which] = snap

    trace_thread = sampler = None
    progress, sampling = [], threading.Event()
    if tracer.enabled:
        # the traced run alone also samples the engine's token counter
        # every 25 ms and prints the longest time without a new token, so
        # that a stall can be told from slow steps; no end-to-end run
        # carries a thread that no metric reads
        def sample_progress():
            while not sampling.wait(0.025):
                progress.append((time.monotonic(), read_tokens()))
        sampler = threading.Thread(target=sample_progress, daemon=True)
        sampler.start()

        def traced():
            time.sleep(float(traffic.get("lead_in_s", 0))
                       + max(0.0, (seconds - TRACE_SECONDS) / 2))
            tracer.start()
            edges["trace_start"] = _counters(engine)
            time.sleep(min(TRACE_SECONDS, seconds))
            edges["trace_end"] = _counters(engine)
            tracer.stop()
        trace_thread = threading.Thread(target=traced, daemon=True)
        trace_thread.start()

    if traffic["loop"] == "open":
        recs, late, t0 = loadgen.drive_open(engine, reqs, seconds, edge)
    else:
        recs, late, t0 = loadgen.drive_closed(
            engine, reqs, seconds, traffic["lead_in_s"],
            traffic["clients"], edge)
    if trace_thread is not None:
        trace_thread.join()
        sampling.set()
        sampler.join()

    t_end = t0 + seconds
    if traffic["loop"] == "open":
        sample = [r for r in recs if r.phase == "window"]
    else:
        sample = [r for r in recs
                  if r.done is not None and t0 <= r.done < t_end]
    sampled = {id(r) for r in sample}
    problems = []
    for r in sample:
        if r.error is not None:
            problems.append(f"request {r.idx}: {r.error}")
        elif r.tokens is None:
            problems.append(f"request {r.idx}: never settled")
        elif r.n_out != r.max_new:
            problems.append(f"request {r.idx}: {r.n_out} tokens, "
                            f"{r.max_new} asked for")
        elif r.tokens.min() < 0 \
                or r.tokens.max() >= system.cfg.vocab_size:
            problems.append(f"request {r.idx}: token id out of range")
    met = [r for r in recs if r.idx % len(reqs) == probe
           and r.tokens is not None]
    if not met:
        problems.append("the probe request never ran inside the mix")
    elif not all(np.array_equal(r.tokens, alone) for r in met):
        problems.append("the probe request alone != inside the mix")
    moved = {k: edges["end"].get(k, 0) - edges["start"].get(k, 0)
             for k in MUST_BE_ZERO + ("compiles",)}
    problems += [f"{k} moved by {v} inside the window"
                 for k, v in moved.items() if v]
    try:
        engine.assert_no_recompiles()
    except AssertionError as e:
        problems.append(str(e)[:200])

    if late:
        print("generator lateness ms: p50 %.3f  p99 %.3f  max %.3f" % (
            1e3 * loadgen.percentile(late, 50),
            1e3 * loadgen.percentile(late, 99), 1e3 * max(late)),
            flush=True)
    print("counters over the window:",
          {k: edges["end"][k] - edges["start"][k]
           for k in ("generated_tokens_total", "prefill_total",
                     "decode_batches_total", "requests_total",
                     "responses_total", "page_wait_total", "compiles")},
          "outstanding at the edges:",
          [edges[w]["queue_depth"] + edges[w]["active_slots"]
           for w in ("start", "end")], flush=True)
    buckets = {}
    for r in recs:
        if r.first_token is not None and t0 <= r.first_token < t_end:
            size = reqs[r.idx % len(reqs)]["prompt"].size
            b = min(x for x in engine.config.prompt_buckets if x >= size)
            buckets[b] = buckets.get(b, 0) + 1
    print("prefills inside the window by bucket:", buckets,
          " host clocks over the window:",
          {k: round(edges["end"][k] - edges["start"][k], 3)
           for k in _host_clocks()}, flush=True)
    stall = None
    if sampler is not None:
        stall = loadgen.longest_stall(progress, t0, t_end)
        print("longest time without a new token inside the window: "
              "%.1f ms, %.1f s into it" % (1e3 * stall[0], stall[1] - t0),
              flush=True)
    c = engine.config
    run = {
        "kind": "serve", "loop": traffic["loop"],
        "requests": [dict(r.as_dict(), in_sample=id(r) in sampled,
                          prompt_len=int(reqs[r.idx % len(reqs)]
                                         ["prompt"].size))
                     for r in recs],
        "t0": t0, "t_end": t_end, "edges": edges,
        "offered": offered, "lateness_s": late,
        "engine": {"max_batch": c.max_batch, "decode_block": c.decode_block,
                   "page_size": c.page_size,
                   "pool_pages": engine.allocator.usable_pages},
        "attempted": len(sample),
        "failed": sum(1 for r in sample
                      if r.error is not None or r.tokens is None),
        "problems": problems,
    }
    # every latency a reader may want, whichever of them the cell judges
    ttft, tpot = _requests.ttft_ms(run), _requests.tpot_ms(run)
    print("latency over the sample, ms:", {
        f"{name}_p{q}": round(loadgen.percentile(v, q), 3)
        for name, v in (("ttft", ttft), ("tpot", tpot)) if v
        for q in (50, 90)}, flush=True)
    return run
