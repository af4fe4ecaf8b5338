"""Llama-3-style decoder-only LLM — the flagship model.

The stretch config: a modern decoder-only LLM built
entirely on the Program IR (embedding → [rms_norm → GQA attention with
rope + flash/ring kernel → rms_norm → SwiGLU MLP] × L → rms_norm →
lm_head → softmax_with_cross_entropy), with Megatron-style tensor-
parallel shardings and dp/sp batch/sequence shardings annotated on the
program so the ParallelExecutor runs it SPMD over a dp×tp(×sp) mesh.
"""
from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

from .. import layers
from ..layers import transformer as tfl
from ..ops.transformer_ops import decode_in_place
from ..param_attr import ParamAttr
from .. import initializer as init_mod

__all__ = ["LlamaConfig", "LLAMA3_8B", "LLAMA_TINY", "build_llama",
           "build_llama_generator", "build_llama_spec_generator",
           "build_llama_paged_programs", "PagedDecodePrograms",
           "quantize_generator_weights", "stack_generator_weights",
           "random_int8_generator_weights",
           "save_decode_model", "load_decode_model"]


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14336
    rope_base: float = 500000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # MoE: >0 turns every FFN into a mixture of this many SwiGLU experts
    # (GShard top-k routing, expert-parallel over the mesh 'ep' axis)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01

    def cache_spec(self):
        """A token's cache entries in one layer, [(shape, dtype)]: a K
        and a V of [kv_heads, head_dim]."""
        entry = (self.n_kv_heads, self.dim // self.n_heads)
        return [(entry, self.dtype), (entry, self.dtype)]

    def build_paged_programs(self, **geometry):
        """What DecodeEngine asks of a model: its paged step programs
        (build_llama_paged_programs)."""
        return build_llama_paged_programs(self, **geometry)


LLAMA3_8B = LlamaConfig()
LLAMA_TINY = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, ffn_hidden=128, dtype="float32")


def _linear(x, out_dim, name):
    return layers.fc(x, size=out_dim, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(
                         name=name,
                         initializer=init_mod.Normal(0.0, 0.02)))


def build_llama(cfg, tokens, targets=None, shard_tp=False, shard_sp=False,
                shard_dp=False, shard_pp=False, pp_n_micro=0,
                pp_schedule="gpipe", fused_head_chunk=0, scan_unroll=1,
                remat=True):
    """Builds the forward (and loss if ``targets``) graph.

    tokens: int data var [batch, seq]. Returns (logits, avg_loss|None).
    ``shard_*`` annotate PartitionSpecs for the corresponding mesh axes.
    ``shard_pp`` builds the decoder stack as one layer-stacked op whose
    stage axis shards over the mesh 'pp' axis (GPipe microbatch schedule
    — see ops/transformer_ops.py llama_decoder_stack); embedding and
    lm_head stay replicated outside the pipeline. ``pp_n_micro``:
    microbatches for the schedule (0 → one per stage).
    ``fused_head_chunk`` > 0 computes the loss with the vocab-chunked
    fused lm-head cross entropy (never materializing [tokens, vocab]
    logits — essential at 128k vocab); logits are then returned as
    None (requires ``targets``).
    ``pp_schedule``: with shard_pp, "gpipe" (default — AD through the
    microbatch schedule) or "1f1b" (the PipeDream-flush interleave:
    backward runs inside the schedule, ≤n_stages in-flight
    activations; requires ``targets``, returns logits None, and folds
    final norm + lm head + loss into the pipelined op).
    """
    if pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pp_schedule {pp_schedule!r}")
    if pp_schedule == "1f1b" and not shard_pp:
        raise ValueError("pp_schedule='1f1b' requires shard_pp=True")
    if pp_schedule == "1f1b" and targets is None:
        raise ValueError("pp_schedule='1f1b' requires targets — the "
                         "loss lives inside the pipelined op")
    # 1f1b's in-pipeline loss is itself vocab-chunked;
    # fused_head_chunk just selects the chunk size there
    if fused_head_chunk and targets is None:
        raise ValueError("fused_head_chunk requires targets")
    if shard_pp and cfg.moe_experts > 0:
        raise ValueError("shard_pp does not compose with moe_experts — "
                         "pick pipeline or expert parallelism per stack")
    if shard_pp and (shard_tp or shard_sp):
        raise ValueError("shard_pp composes with dp (microbatch axis), "
                         "not with tp/sp — stage weights are pp-sharded "
                         "and the stacked decoder runs flash (not ring) "
                         "attention inside the pipeline")
    dt = cfg.dtype
    hd = cfg.dim // cfg.n_heads
    prog = tokens.block.program
    gb = prog.global_block()

    aux_losses = []
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.dim],
                           param_attr=ParamAttr(
                               name="tok_emb",
                               initializer=init_mod.Normal(0.0, 0.02)),
                           dtype=dt)
    h = emb
    if shard_pp and pp_schedule == "1f1b":
        loss = tfl.llama_stack_1f1b_loss(
            h, targets, vocab_size=cfg.vocab_size,
            n_layers=cfg.n_layers, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, ffn_hidden=cfg.ffn_hidden,
            rope_base=cfg.rope_base, epsilon=cfg.norm_eps,
            n_micro=pp_n_micro, scan_unroll=scan_unroll, remat=remat,
            loss_chunk=fused_head_chunk or 8192, name="blocks")
        spec = [("dp",) if shard_dp else None, None]
        tokens.sharding = P(*spec)
        targets.sharding = P(*spec)
        return None, loss
    if shard_pp:
        h = tfl.llama_decoder_stack(
            h, n_layers=cfg.n_layers, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, ffn_hidden=cfg.ffn_hidden,
            rope_base=cfg.rope_base, epsilon=cfg.norm_eps,
            n_micro=pp_n_micro, scan_unroll=scan_unroll, remat=remat,
            name="blocks")
        return _finish(cfg, gb, h, tokens, targets, aux_losses,
                       shard_tp=False, shard_sp=shard_sp,
                       shard_dp=shard_dp,
                       fused_head_chunk=fused_head_chunk)
    for i in range(cfg.n_layers):
        pre = tfl.rms_norm(h, epsilon=cfg.norm_eps,
                           param_attr=ParamAttr(name=f"l{i}.attn_norm"))
        q = _linear(pre, cfg.n_heads * hd, f"l{i}.wq")
        k = _linear(pre, cfg.n_kv_heads * hd, f"l{i}.wk")
        v = _linear(pre, cfg.n_kv_heads * hd, f"l{i}.wv")
        q = layers.reshape(q, [0, 0, cfg.n_heads, hd])
        k = layers.reshape(k, [0, 0, cfg.n_kv_heads, hd])
        v = layers.reshape(v, [0, 0, cfg.n_kv_heads, hd])
        q = tfl.rope(q, base=cfg.rope_base)
        k = tfl.rope(k, base=cfg.rope_base)
        attn = tfl.multihead_attention(q, k, v, causal=True)
        attn = layers.reshape(attn, [0, 0, cfg.n_heads * hd])
        o = _linear(attn, cfg.dim, f"l{i}.wo")
        h = layers.elementwise_add(h, o)

        pre2 = tfl.rms_norm(h, epsilon=cfg.norm_eps,
                            param_attr=ParamAttr(name=f"l{i}.mlp_norm"))
        if cfg.moe_experts > 0:
            mlp, aux = tfl.moe_ffn(
                pre2, num_experts=cfg.moe_experts,
                hidden_dim=cfg.ffn_hidden, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                name=f"l{i}.moe")
            aux_losses.append(aux)
        else:
            gate = tfl.silu(_linear(pre2, cfg.ffn_hidden, f"l{i}.w_gate"))
            up = _linear(pre2, cfg.ffn_hidden, f"l{i}.w_up")
            mlp = _linear(layers.elementwise_mul(gate, up), cfg.dim,
                          f"l{i}.w_down")
        h = layers.elementwise_add(h, mlp)

    return _finish(cfg, gb, h, tokens, targets, aux_losses,
                   shard_tp=shard_tp, shard_sp=shard_sp,
                   shard_dp=shard_dp, fused_head_chunk=fused_head_chunk)


def _finish(cfg, gb, h, tokens, targets, aux_losses, shard_tp, shard_sp,
            shard_dp, fused_head_chunk=0):
    h = tfl.rms_norm(h, epsilon=cfg.norm_eps,
                     param_attr=ParamAttr(name="final_norm"))
    logits = None
    if not fused_head_chunk:
        logits = _linear(h, cfg.vocab_size, "lm_head")

    batch_axes = []
    if shard_dp:
        batch_axes.append("dp")
    tok_spec = [tuple(batch_axes) or None]
    if shard_sp:
        tok_spec.append("sp")
    else:
        tok_spec.append(None)
    tokens.sharding = P(*tok_spec)

    avg_loss = None
    if targets is not None:
        targets.sharding = P(*tok_spec)
        if fused_head_chunk:
            loss = tfl.fused_head_cross_entropy(
                h, targets, cfg.vocab_size,
                chunk_size=fused_head_chunk, head_name="lm_head")
        else:
            loss = layers.softmax_with_cross_entropy(logits, targets)
        avg_loss = layers.mean(loss)
        if aux_losses:
            total_aux = aux_losses[0]
            for a in aux_losses[1:]:
                total_aux = layers.elementwise_add(total_aux, a)
            avg_loss = layers.elementwise_add(
                avg_loss, layers.scale(total_aux, cfg.moe_aux_weight))

    # ------ sharding annotations — AFTER every parameter exists (the
    # fused head creates lm_head inside the loss construction) --------
    if shard_tp:
        for name, spec in _tp_spec_table(cfg).items():
            if name in gb.vars:
                gb.vars[name].sharding = spec
    return logits, avg_loss


def build_llama_generator(cfg, tokens, max_new_tokens,
                          temperature=0.0, top_k=0, top_p=1.0,
                          quantize=False, eos_id=None, pad_id=0,
                          shard_tp=False, shard_dp=False,
                          unroll_layers=False, decode_unroll=1,
                          kv_int8=False, return_probs=False):
    """Greedy KV-cache generation program for a model trained with
    ``build_llama(shard_pp=True)`` (the layer-stacked weight layout):
    build this in its OWN program, then run it with the trained scope —
    parameter names match, so no conversion step exists. A model
    trained with per-layer weights (the unstacked path — MoE configs
    train this way) first converts its scope with
    :func:`stack_generator_weights`. MoE FFNs decode with drop-free
    top-k routing (ops/moe.py moe_apply_no_drop — matching the test
    mode of training's moe_ffn op, so cached decoding reproduces the
    eval forward). Returns the [batch, prompt+max_new] token
    variable; with ``return_probs=True``, returns ``(tokens, probs)``
    where ``probs`` is the first decode step's [batch, vocab]
    distribution (computed entirely from the prefill cache — the
    probability-level closeness instrument for quantized variants)."""
    out = tfl.llama_generate(
        tokens, vocab_size=cfg.vocab_size, dim=cfg.dim,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, ffn_hidden=cfg.ffn_hidden,
        max_new_tokens=max_new_tokens, rope_base=cfg.rope_base,
        epsilon=cfg.norm_eps, dtype=cfg.dtype,
        temperature=temperature, top_k=top_k, top_p=top_p,
        name="blocks", quantize=quantize, eos_id=eos_id, pad_id=pad_id,
        moe_experts=cfg.moe_experts, moe_top_k=cfg.moe_top_k,
        unroll_layers=unroll_layers, decode_unroll=decode_unroll,
        kv_int8=kv_int8, return_probs=return_probs)
    probs = None
    if return_probs:
        out, probs = out
    # multi-chip serving shardings: Megatron column/row splits on the
    # stacked [L, in, out] weights over 'tp', batch over 'dp'; GSPMD
    # partitions the fused prefill+decode program (KV caches follow the
    # kv-head split, all-reduces land after wo/w_down)
    if shard_tp:
        gb = tokens.block.program.global_block()
        col, row = P(None, None, "tp"), P(None, "tp", None)
        table = {"blocks.wq": col, "blocks.wk": col, "blocks.wv": col,
                 "blocks.wo": row, "blocks.w_gate": col,
                 "blocks.w_up": col, "blocks.w_down": row,
                 # MoE experts split Megatron-style INSIDE each expert
                 # (hidden dim column/row); the tiny router replicates
                 "blocks.moe_w_gate": P(None, None, None, "tp"),
                 "blocks.moe_w_up": P(None, None, None, "tp"),
                 "blocks.moe_w_down": P(None, None, "tp", None),
                 "tok_emb": P(None, "tp"), "lm_head": P(None, "tp")}
        for name, spec in table.items():
            if name in gb.vars:
                gb.vars[name].sharding = spec
    if shard_dp:
        tokens.sharding = P("dp", None)
        out.sharding = P("dp", None)
    if return_probs:
        return out, probs
    return out


def build_llama_spec_generator(cfg, draft_cfg, tokens, max_new_tokens,
                               gamma=4, unroll_layers=False,
                               temperature=0.0, top_k=0, top_p=1.0,
                               eos_id=None, pad_id=0,
                               return_stats=False,
                               name="blocks", draft_name="draft"):
    """Speculative decoding: ``draft_cfg`` (a smaller LlamaConfig)
    proposes ``gamma`` tokens per round, ``cfg`` (the target) verifies
    them in one cached forward, at one target forward per ~(accepted+1)
    tokens. At ``temperature`` 0 (default) the output tokens are
    EXACTLY ``build_llama_generator(cfg, ...)``'s greedy output
    (pinned by test). At ``temperature`` > 0 this is speculative
    SAMPLING (rejection resampling, Leviathan et al. / Chen et al.):
    every emitted token is distributed exactly as the plain
    generator's sampler with the same ``temperature``/``top_k``/
    ``top_p`` (distribution-equal — pinned statistically by test —
    but not bitwise-equal: the rng is consumed differently).
    Target weights use the trained ``build_llama`` names. Draft
    weights live under ``{draft_name}.*``: train the draft as a normal
    ``build_llama(draft_cfg, ...)`` model in its own scope, then copy
    its stacked tensors into the serving scope under the prefixed
    names (the tensor list is GENERATOR_STACK_SUFFIXES +
    GENERATOR_SINGLETON_NAMES; :func:`copy_weights_as_draft` does the
    same-scope 'perfect draft' form). Both models must
    share the tokenizer (same vocab_size). The reference era has no
    speculative path — beyond-parity serving, TPU-first (two KV
    caches, one bounded lax.while_loop, zero host round trips).

    ``eos_id``/``pad_id`` follow ``build_llama_generator``'s masking
    convention (sequences that emit eos keep emitting pad; pinned
    equal by test). Design-outs (use ``build_llama_generator`` for
    these): int8 scopes (guarded with a loud error at run time) and
    MoE configs."""
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"target and draft must share a vocabulary: "
            f"{cfg.vocab_size} vs {draft_cfg.vocab_size}")
    if cfg.moe_experts or draft_cfg.moe_experts:
        raise NotImplementedError(
            "speculative decoding with MoE configs is not implemented "
            "(the dense path is; route MoE serving through "
            "build_llama_generator)")
    result = tfl.llama_spec_generate(
        tokens, vocab_size=cfg.vocab_size,
        max_new_tokens=max_new_tokens, gamma=gamma,
        temperature=temperature, top_k=top_k, top_p=top_p,
        return_stats=return_stats,
        dim=cfg.dim, n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, ffn_hidden=cfg.ffn_hidden,
        draft_dim=draft_cfg.dim, draft_n_layers=draft_cfg.n_layers,
        draft_n_heads=draft_cfg.n_heads,
        draft_n_kv_heads=draft_cfg.n_kv_heads,
        draft_ffn_hidden=draft_cfg.ffn_hidden,
        rope_base=cfg.rope_base, epsilon=cfg.norm_eps, dtype=cfg.dtype,
        # the draft keeps ITS OWN rope/eps/dtype — serving it under the
        # target's would silently wreck its proposals (and the speedup)
        draft_rope_base=draft_cfg.rope_base,
        draft_epsilon=draft_cfg.norm_eps, draft_dtype=draft_cfg.dtype,
        unroll_layers=unroll_layers, eos_id=eos_id, pad_id=pad_id,
        name=name, draft_name=draft_name)
    # return_stats: (tokens, rounds, emitted) — (emitted - 1) /
    # rounds vs the (gamma+1) ceiling is the achieved speculation
    # efficiency (the prefill token costs no verification round), the
    # number a deployment tunes gamma (and its draft) against
    return result


class PagedDecodePrograms:
    """The step-function program set the continuous-batching decode
    engine runs (serving/decode_engine.py): one prefill program per
    prompt-length bucket, one decode-step program, and optionally one
    speculative-round program — every shape in them static, so the
    whole set compiles exactly once per (model config, max_batch) and
    never again as requests churn through the slots.

    ``prefill`` maps bucket length -> a bundle dict with the program,
    feed var names, and fetch vars; ``decode``/``spec`` are single
    bundles. A bundle's feeds end with the model's cache pools and its
    fetches are (token outputs, the pools, then what ``extras`` names:
    ``logits``, ``picks``, ``stats``); ``pools`` says which pools those
    are where not the target's (``draft``, ``both``); the decode
    bundle's ``in_place`` says whether its steps attend their pages through
    a Pallas kernel (ops/transformer_ops.py decode_in_place: a report; the
    steps run against the pools either way). ``pool_specs`` (and ``draft_pool_specs``
    when spec) are the (shape, dtype) of each pool the engine allocates
    and round-trips through every dispatch: ``[L, n_pages, page_size]``
    followed by one entry of the model's ``cache_spec()``. ``stats``
    names the counters a ``stats`` fetch holds, in its order.

    Every pool is of the ``sequence`` cache kind (pages for as long as
    the request lives, ``pages_per_seq`` a row, one table) unless
    ``kinds`` says otherwise: cache kind -> ``{"pages_per_seq": pages a
    row holds of it, "n_pages": the kind's pages, null page included,
    "pools": the indices in pool_specs of the kind's pools, "table": the
    (op slot, feed name) of the rows' table of it}``, by the name the
    allocator knows the kind by and in the order of the tables, which
    every program of such a model takes behind the page table. ``window``
    (a model with window attention layers) is a RING of pages a row
    (``"window"``: w); ``state`` (a model with state-space layers) ONE
    entry a request whatever its length (``pages_per_seq`` 1; ``"unit"``:
    what the engine's gauges call its pages, "entries"). The engine
    reads a kind from here and nowhere else."""

    def __init__(self, cfg, draft_cfg, page_size, pages_per_seq,
                 n_pages, max_batch, prefill, decode, spec, pool_specs,
                 draft_pool_specs, draft_prefill=None, chunk=None,
                 chunk_size=None, stats=(), kinds=None):
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.n_pages = n_pages
        self.max_batch = max_batch
        self.seq_capacity = pages_per_seq * page_size
        self.prefill = prefill
        self.draft_prefill = draft_prefill
        self.decode = decode
        self.spec = spec
        self.chunk = chunk              # chunked-prefill bundle or None
        self.chunk_size = chunk_size
        self.pool_specs = pool_specs
        self.draft_pool_specs = draft_pool_specs
        self.stats = tuple(stats)
        self.kinds = dict(kinds or {})


def prefill_buckets_reached(prompt_buckets, chunk_size):
    """The buckets a whole-prompt prefill can land in: all of them, or,
    with chunked prefill, those that some prompt of at most
    ``chunk_size`` tokens pads to (longer prompts go in slices through
    the chunk program, so a program for a larger bucket would be built,
    compiled and warmed for no request)."""
    buckets = sorted(set(int(b) for b in prompt_buckets))
    if chunk_size is None:
        return buckets
    return [b for i, b in enumerate(buckets)
            if i == 0 or buckets[i - 1] < int(chunk_size)]


def cache_pool_specs(cfg, n_pages, page_size):
    """(shape, dtype) of each cache pool of ``cfg``: ``[layers, n_pages,
    page_size]`` before each entry of its ``cache_spec()``."""
    return [([cfg.n_layers, n_pages, page_size] + list(entry), dtype)
            for entry, dtype in cfg.cache_spec()]


def build_llama_paged_programs(cfg, *, max_batch, page_size, n_pages,
                               pages_per_seq, prompt_buckets,
                               decode_block=1, quantize=False,
                               draft_cfg=None, gamma=4,
                               chunk_size=None):
    """Builds the paged-KV step programs for ``cfg`` (dense configs
    only): prefill-into-slot per prompt bucket, a ``decode_block``-step
    decode program, and (with ``draft_cfg``) a speculative-round
    program. Parameter names are the generator serving layout
    (``blocks.* / tok_emb / final_norm / lm_head``, draft under
    ``draft.*``), so a scope prepared for ``build_llama_generator`` —
    trained, stacked, optionally ``quantize_generator_weights``'d —
    serves these programs directly. The scope must already hold the
    weights: the throwaway startup programs built here are never
    returned, by design (the engine never initializes weights)."""
    if cfg.moe_experts > 0 or (draft_cfg is not None
                               and draft_cfg.moe_experts > 0):
        raise NotImplementedError(
            "the Llama paged programs are dense: softmax-routed experts "
            "under GQA have no paged form (models/latent_moe.py has the "
            "routed form the engine serves)")
    if draft_cfg is not None and quantize:
        raise NotImplementedError(
            "speculative paged decoding is float-only (same design-out "
            "as llama_spec_generate); drop quantize or draft_cfg")
    if draft_cfg is not None and draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"target and draft must share a vocabulary: "
            f"{cfg.vocab_size} vs {draft_cfg.vocab_size}")
    from ..core import framework
    pool_specs = cache_pool_specs(cfg, n_pages, page_size)
    kv_shape = pool_specs[0][0]
    common = dict(vocab_size=cfg.vocab_size, dim=cfg.dim,
                  n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                  n_kv_heads=cfg.n_kv_heads, ffn_hidden=cfg.ffn_hidden,
                  page_size=page_size, rope_base=cfg.rope_base,
                  epsilon=cfg.norm_eps, dtype=cfg.dtype)

    def _data(name, shape, dtype):
        return layers.data(name=name, shape=list(shape), dtype=dtype,
                           append_batch_size=False)

    prefill = {}
    buckets = prefill_buckets_reached(prompt_buckets, chunk_size)
    for bucket in buckets:
        main = framework.Program()
        with framework.program_guard(main, framework.Program()), \
                framework.unique_name.guard():
            tokens = _data("pp_tokens", [1, bucket], "int64")
            lens = _data("pp_lens", [1], "int32")
            table = _data("pp_table", [1, pages_per_seq], "int32")
            kp = _data("pp_kpages", kv_shape, cfg.dtype)
            vp = _data("pp_vpages", kv_shape, cfg.dtype)
            nxt, kp_out, vp_out = tfl.llama_paged_prefill(
                tokens, lens, table, kp, vp, quantize=quantize,
                **common)
        prefill[bucket] = {
            "program": main.clone(for_test=True),
            "feeds": ("pp_tokens", "pp_lens", "pp_table",
                      "pp_kpages", "pp_vpages"),
            "fetch": [nxt, kp_out, vp_out]}

    main = framework.Program()
    with framework.program_guard(main, framework.Program()), \
            framework.unique_name.guard():
        tokens = _data("dc_tokens", [max_batch], "int64")
        positions = _data("dc_positions", [max_batch], "int32")
        table = _data("dc_table", [max_batch, pages_per_seq], "int32")
        kp = _data("dc_kpages", kv_shape, cfg.dtype)
        vp = _data("dc_vpages", kv_shape, cfg.dtype)
        out, kp_out, vp_out = tfl.llama_paged_decode(
            tokens, positions, table, kp, vp, steps=decode_block,
            quantize=quantize, **common)
    decode = {"program": main.clone(for_test=True),
              "feeds": ("dc_tokens", "dc_positions", "dc_table",
                        "dc_kpages", "dc_vpages"),
              "fetch": [out, kp_out, vp_out],
              "in_place": decode_in_place(
                  "gqa", None, [shape for shape, _ in pool_specs])}

    chunk = None
    if chunk_size is not None:
        # chunked prefill: ONE executable for every slice of every
        # prompt — batch 1 (a chunk is one request's slice; slices of
        # different requests are separate dispatches so admission stays
        # per-request), width `chunk_size`, per-row offset fed as data.
        # Partial final slices ride the same shape via Lens padding,
        # so chunk churn can never trigger a recompile.
        cs = int(chunk_size)
        if cs < 1:
            raise ValueError(f"chunk_size must be >= 1, got {cs}")
        main = framework.Program()
        with framework.program_guard(main, framework.Program()), \
                framework.unique_name.guard():
            tokens = _data("ck_tokens", [1, cs], "int64")
            lens = _data("ck_lens", [1], "int32")
            offsets = _data("ck_offsets", [1], "int32")
            table = _data("ck_table", [1, pages_per_seq], "int32")
            kp = _data("ck_kpages", kv_shape, cfg.dtype)
            vp = _data("ck_vpages", kv_shape, cfg.dtype)
            nxt, kp_out, vp_out = tfl.llama_paged_prefill_chunk(
                tokens, lens, offsets, table, kp, vp,
                quantize=quantize, **common)
        chunk = {"program": main.clone(for_test=True),
                 "feeds": ("ck_tokens", "ck_lens", "ck_offsets",
                           "ck_table", "ck_kpages", "ck_vpages"),
                 "fetch": [nxt, kp_out, vp_out]}

    spec = None
    draft_prefill = None
    draft_pool_specs = None
    if draft_cfg is not None:
        draft_pool_specs = cache_pool_specs(draft_cfg, n_pages, page_size)
        draft_kv_shape = draft_pool_specs[0][0]
        # the draft prefills its own paged cache over the same prompt
        # (and the same page indices — one table serves both pools)
        draft_prefill = {}
        for bucket in buckets:
            main = framework.Program()
            with framework.program_guard(main, framework.Program()), \
                    framework.unique_name.guard():
                tokens = _data("dp_tokens", [1, bucket], "int64")
                lens = _data("dp_lens", [1], "int32")
                table = _data("dp_table", [1, pages_per_seq], "int32")
                kp = _data("dp_kpages", draft_kv_shape, draft_cfg.dtype)
                vp = _data("dp_vpages", draft_kv_shape, draft_cfg.dtype)
                nxt, kp_out, vp_out = tfl.llama_paged_prefill(
                    tokens, lens, table, kp, vp,
                    vocab_size=draft_cfg.vocab_size, dim=draft_cfg.dim,
                    n_layers=draft_cfg.n_layers,
                    n_heads=draft_cfg.n_heads,
                    n_kv_heads=draft_cfg.n_kv_heads,
                    ffn_hidden=draft_cfg.ffn_hidden,
                    page_size=page_size, rope_base=draft_cfg.rope_base,
                    epsilon=draft_cfg.norm_eps, dtype=draft_cfg.dtype,
                    name="draft", emb_name="draft.tok_emb",
                    final_norm_name="draft.final_norm",
                    head_name="draft.lm_head")
            draft_prefill[bucket] = {
                "program": main.clone(for_test=True),
                "feeds": ("dp_tokens", "dp_lens", "dp_table",
                          "dp_kpages", "dp_vpages"),
                "fetch": [nxt, kp_out, vp_out], "pools": "draft"}
        main = framework.Program()
        with framework.program_guard(main, framework.Program()), \
                framework.unique_name.guard():
            tokens = _data("sp_tokens", [max_batch], "int64")
            prev = _data("sp_prev", [max_batch], "int64")
            positions = _data("sp_positions", [max_batch], "int32")
            table = _data("sp_table", [max_batch, pages_per_seq],
                          "int32")
            kp = _data("sp_kpages", kv_shape, cfg.dtype)
            vp = _data("sp_vpages", kv_shape, cfg.dtype)
            dkp = _data("sp_draft_kpages", draft_kv_shape,
                        draft_cfg.dtype)
            dvp = _data("sp_draft_vpages", draft_kv_shape,
                        draft_cfg.dtype)
            spec_common = dict(common)
            del spec_common["dtype"]
            outs = tfl.llama_paged_spec_step(
                tokens, prev, positions, table, kp, vp, dkp, dvp,
                draft_dim=draft_cfg.dim,
                draft_n_layers=draft_cfg.n_layers,
                draft_n_heads=draft_cfg.n_heads,
                draft_n_kv_heads=draft_cfg.n_kv_heads,
                draft_ffn_hidden=draft_cfg.ffn_hidden,
                gamma=gamma, dtype=cfg.dtype,
                draft_rope_base=draft_cfg.rope_base,
                draft_epsilon=draft_cfg.norm_eps,
                draft_dtype=draft_cfg.dtype, **spec_common)
        spec = {"program": main.clone(for_test=True),
                "feeds": ("sp_tokens", "sp_prev", "sp_positions",
                          "sp_table", "sp_kpages", "sp_vpages",
                          "sp_draft_kpages", "sp_draft_vpages"),
                "fetch": list(outs), "pools": "both"}

    return PagedDecodePrograms(
        cfg, draft_cfg, page_size, pages_per_seq, n_pages, max_batch,
        prefill, decode, spec, pool_specs, draft_pool_specs,
        draft_prefill=draft_prefill, chunk=chunk,
        chunk_size=None if chunk is None else int(chunk_size))


# scope-name suffixes of the layer-stacked generator weights (the
# lowercase twins of ops/transformer_ops._STACK_SLOTS) plus the
# singleton tensors — the full tensor set a generator serves from
GENERATOR_STACK_SUFFIXES = ("attn_norm", "wq", "wk", "wv", "wo",
                            "mlp_norm", "w_gate", "w_up", "w_down")
GENERATOR_SINGLETON_NAMES = ("tok_emb", "final_norm", "lm_head")


def copy_weights_as_draft(scope, name="blocks", draft_name="draft"):
    """Alias the target generator's tensors under the ``{draft_name}.*``
    names llama_spec_generate reads — the 'perfect draft' arrangement
    (acceptance ~1; used by tests and the bench's copy mode). The one
    list of what a draft needs lives HERE: growing the generator's
    tensor set must update these constants, and every consumer follows."""
    for suffix in GENERATOR_STACK_SUFFIXES:
        scope.set(f"{draft_name}.{suffix}",
                  scope.find_var(f"{name}.{suffix}"))
    for nm in GENERATOR_SINGLETON_NAMES:
        scope.set(f"{draft_name}.{nm}", scope.find_var(nm))


_QUANT_SUFFIXES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def stack_generator_weights(cfg, scope=None, name="blocks"):
    """Convert a scope trained with the PER-LAYER weight layout (the
    unstacked build_llama path — tensor/sequence-parallel and MoE
    configs) into the layer-stacked ``{name}.*`` arrays the fused
    generator consumes: ``l{i}.wq [d, H*hd]`` -> ``blocks.wq
    [L, d, H*hd]`` etc. Norms and MoE tables stack the same way; the
    per-layer entries stay in the scope untouched."""
    import numpy as np
    from ..core.executor import global_scope
    scope = scope or global_scope()

    def stack(fmt):
        rows = []
        for i in range(cfg.n_layers):
            v = scope.find_var(fmt.format(i=i))
            if v is None:
                raise KeyError(f"missing trained weight {fmt.format(i=i)}")
            rows.append(np.asarray(v))
        return np.stack(rows)

    suffixes = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"]
    if cfg.moe_experts > 0:
        moe_map = {"moe_router": "moe.router", "moe_w_gate": "moe.w_gate",
                   "moe_w_up": "moe.w_up", "moe_w_down": "moe.w_down"}
        for stacked_sfx, layer_sfx in moe_map.items():
            scope.set(f"{name}.{stacked_sfx}",
                      stack("l{i}." + layer_sfx))
    else:
        suffixes += ["w_gate", "w_up", "w_down"]
    for sfx in suffixes:
        scope.set(f"{name}.{sfx}", stack("l{i}." + sfx))


def quantize_generator_weights(scope=None, name="blocks",
                               head_name="lm_head"):
    """Rewrite a trained scope's stacked decoder matmul weights and lm
    head to weight-only int8 (symmetric, per layer x output channel),
    writing ``<w>@scale`` float companions — the serving scope for
    ``build_llama_generator(..., quantize=True)``. Embedding and norm
    weights stay float (a handful of rows / vectors; quantizing them
    saves nothing decode is bound by). See
    transpiler.QuantizeTranspiler for the generic per-op program form
    this mirrors on the fused generator."""
    import numpy as np
    from ..core.executor import global_scope
    scope = scope or global_scope()

    def _q(w, axis):
        # reduce over the CONTRACTED axis only: leading L (and, for
        # 4-D MoE expert stacks [L, E, in, out], the E axis) keep their
        # own per-layer/per-expert scales
        red = tuple(i for i in range(w.ndim)
                    if i != axis and i >= w.ndim - 2)
        scale = np.max(np.abs(w), axis=red, keepdims=True) / 127.0
        scale = np.maximum(scale, 1e-10).astype(np.float32)
        wq = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        return wq, scale

    moe = scope.find_var(f"{name}.moe_router") is not None
    suffixes = (("wq", "wk", "wv", "wo",
                 "moe_w_gate", "moe_w_up", "moe_w_down") if moe
                else _QUANT_SUFFIXES)
    for suffix in suffixes:
        n = f"{name}.{suffix}"
        v = scope.find_var(n)
        if v is None:
            raise KeyError(
                f"missing {n!r} in scope — run the startup program "
                "(or stack_generator_weights on a trained per-layer "
                "scope) before quantize_generator_weights")
        w = np.asarray(v)               # [L, in, out] / [L, E, in, out]
        wq, scale = _q(w, axis=w.ndim - 1)
        scope.set(n, wq)
        scope.set(n + "@scale", scale)  # [L, 1, out] / [L, E, 1, out]
        # the router stays float: it is tiny and its softmax ranking
        # IS the routing decision
    head = np.asarray(scope.find_var(head_name))        # [D, V]
    hq, hscale = _q(head, axis=1)
    scope.set(head_name, hq)
    scope.set(head_name + "@scale", hscale.reshape(-1))  # [V]


def random_int8_generator_weights(cfg, exe, scope):
    """Fill ``scope`` with a random int8 serving model for a dense
    ``cfg`` in the generator layout, created ON the executor's device:
    one tiny init program per tensor (int8 straight out of
    uniform_random — no float stage, no multi-GB host transfer, init
    transients bounded by one tensor). Per-channel scales are the
    constant 1.6e-4 (the magnitude 0.02-std weights quantize to). For
    benchmarks and smokes that need full-size weights from a seed, not
    a trained model."""
    from ..core import framework
    hd = cfg.dim // cfg.n_heads
    L, D, V, F = cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.ffn_hidden
    int8_shapes = {
        "blocks.wq": [L, D, cfg.n_heads * hd],
        "blocks.wk": [L, D, cfg.n_kv_heads * hd],
        "blocks.wv": [L, D, cfg.n_kv_heads * hd],
        "blocks.wo": [L, cfg.n_heads * hd, D],
        "blocks.w_gate": [L, D, F], "blocks.w_up": [L, D, F],
        "blocks.w_down": [L, F, D], "lm_head": [D, V]}

    def init_one(name, shape, dtype, op_type, **attrs):
        p = framework.Program()
        gb = p.global_block()
        v = gb.create_var(name=name, shape=shape, dtype=dtype,
                          persistable=True)
        gb.append_op(type=op_type, inputs={}, outputs={"Out": [v.name]},
                     attrs={"shape": shape, "dtype": dtype, **attrs})
        exe.run(p, scope=scope)

    for name, shape in int8_shapes.items():
        init_one(name, shape, "int8", "uniform_random",
                 min=-100.0, max=100.0)
        init_one(name + "@scale",
                 [V] if name == "lm_head" else [L, 1, shape[-1]],
                 "float32", "fill_constant", value=1.6e-4)
    init_one("tok_emb", [V, D], cfg.dtype, "gaussian_random", std=0.02)
    for name, shape in (("blocks.attn_norm", [L, D]),
                        ("blocks.mlp_norm", [L, D]),
                        ("final_norm", [D])):
        init_one(name, shape, cfg.dtype, "fill_constant", value=1.0)


def _tp_spec_table(cfg):
    """Megatron splits: qkv/gate/up column-parallel, o/down row-parallel,
    embedding + lm_head vocab/column split."""
    table = {"tok_emb": P(None, "tp"), "lm_head": P(None, "tp")}
    for i in range(cfg.n_layers):
        table[f"l{i}.wq"] = P(None, "tp")
        table[f"l{i}.wk"] = P(None, "tp")
        table[f"l{i}.wv"] = P(None, "tp")
        table[f"l{i}.wo"] = P("tp", None)
        table[f"l{i}.w_gate"] = P(None, "tp")
        table[f"l{i}.w_up"] = P(None, "tp")
        table[f"l{i}.w_down"] = P("tp", None)
    return table


# ---------------------------------------------------------------------------
# decode-model persistence (the artifact a decode worker process loads)
# ---------------------------------------------------------------------------

def save_decode_model(dirname, cfg, scope):
    """Persist a decode-servable model: the LlamaConfig as JSON plus
    every generator-layout scope var as one npz. This is the artifact
    ``python -m paddle_tpu.cluster.proc_worker --decode`` serves — a
    DecodeEngine needs (config, weights), not an inference Program, so
    ``save_inference_model`` is the wrong container for it."""
    import json
    import os

    import numpy as np
    from dataclasses import asdict
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "llama_config.json"), "w") as f:
        json.dump(asdict(cfg), f, indent=1, sort_keys=True)
    params = {}
    for name in scope.keys():
        v = scope.find_var(name)
        if v is None:
            continue
        params[name] = np.asarray(v)
    np.savez(os.path.join(dirname, "params.npz"), **params)
    return dirname


def load_decode_model(dirname):
    """Load a :func:`save_decode_model` directory back into
    ``(LlamaConfig, Scope)`` — ready for
    ``DecodeEngine(cfg, scope=scope)``."""
    import json
    import os

    import numpy as np
    from ..core.executor import Scope
    with open(os.path.join(dirname, "llama_config.json")) as f:
        cfg = LlamaConfig(**json.load(f))
    scope = Scope()
    with np.load(os.path.join(dirname, "params.npz")) as blobs:
        for name in blobs.files:
            scope.set(name, blobs[name])
    return cfg, scope
