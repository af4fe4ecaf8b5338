"""Transformer-family op lowering rules: RMSNorm, rotary embeddings,
fused multi-head attention (flash kernel / ring attention dispatch).

These extend the reference op set the way its contrib fused ops do
(reference paddle/fluid/operators/attention_lstm_op.cc,
fusion_lstm_op.cc etc. are the CUDA-era analogues): the hot path is one
op the compiler can schedule as a unit, instead of a softmax/matmul
chain.
"""
import contextlib
import copy
import functools
import itertools
import math

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from . import delta_rule, short_conv, ssm
from . import pallas_attention as _pa
from .pallas_attention import (flash_attention, masked_attention,
                               paged_flat_decode, paged_flat_usable,
                               paged_packed_usable,
                               paged_gqa_decode, paged_latent_decode,
                               paged_latent_usable, paged_gqa_usable,
                               prefill_fold)


def rms_normalize(x, scale=None, eps=1e-6):
    """f32-accumulated RMS norm, output in x.dtype — shared by the
    rms_norm op and the fused llama_decoder_stack block."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                                    keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    return y.astype(dt)


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    scale = ins["Scale"][0] if ins.get("Scale") else None
    return {"Y": [rms_normalize(ins["X"][0], scale,
                                attrs.get("epsilon", 1e-6))]}


def apply_rope_at(x, positions, base=10000.0, inv_freq=None, factor=1.0):
    """x: [B, T, H, D]; positions: [T] absolute positions shared by the
    batch, or [B, T] per-row positions (the continuous-batching decode
    engine schedules rows at unrelated sequence offsets). Positions may
    be traced values — unlike apply_rope's table slicing, nothing here
    depends on them being static. ``inv_freq`` [D/2] replaces the plain
    ``base ** (-2i / D)`` (yarn_inv_freq); ``factor`` multiplies the
    cosines and sines (YaRN's attention factor, where a model carries it
    on the rotation and not on the softmax's scale)."""
    b, t, h, d = x.shape
    inv = (1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
           if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    freqs = positions.astype(jnp.float32)[..., None] * inv  # [(B,)T, D/2]
    if freqs.ndim == 2:
        cos = jnp.cos(freqs)[None, :, None, :]
        sin = jnp.sin(freqs)[None, :, None, :]
    else:
        cos = jnp.cos(freqs)[:, :, None, :]
        sin = jnp.sin(freqs)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def apply_rope(x, base=10000.0, position_offset=0):
    """x: [B, T, H, D] — rotates feature pairs (d, d + D/2) (neox
    style). Same math as apply_rope_at at positions offset..offset+T."""
    t = x.shape[1]
    return apply_rope_at(x, position_offset + jnp.arange(t), base)


def yarn_inv_freq(dim, base, factor, original_max, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's inverse frequencies for a ``dim``-wide rotary part (Peng et
    al., arXiv:2309.00071, as DeepseekV3YarnRotaryEmbedding computes
    them): pairs that turn more than ``beta_fast`` times over the
    original context keep ``base ** (-2i / dim)``, pairs that turn less
    than ``beta_slow`` times are divided by ``factor``, a linear ramp
    between. numpy, so that it is a constant of the program."""
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(
        np.float32)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def warp_logits(logits, temperature, top_k=0, top_p=1.0):
    """Apply the sampling logits processors — temperature scaling,
    top-k truncation, top-p (nucleus) filtering — to raw logits
    ([..., V]); masked entries go to -1e30. Shared by llama_generate's
    sampler and llama_spec_generate's speculative sampler so the two
    serving paths warp identically (speculative sampling preserves the
    WARPED target distribution, so both sides must apply the same
    processors). temperature must be > 0 (greedy is argmax on raw
    logits)."""
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        # top_p == 0 would otherwise wrap the threshold index to the
        # SMALLEST sorted logit and silently disable filtering
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        sorted_l = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest prefix with cumulative mass >= top_p stays
        cut = jnp.sum(cum - probs < top_p, axis=-1) - 1
        thresh = jnp.take_along_axis(sorted_l, cut[..., None], axis=-1)
        logits = jnp.where(logits < thresh, -1e30, logits)
    return logits


@register_op("rope")
def _rope(ctx, ins, attrs):
    return {"Out": [apply_rope(ins["X"][0], attrs.get("base", 10000.0))]}


def _mesh_flash_attention(qt, kt, vt, causal, scale, mesh):
    """flash_attention under a mesh GSPMD partitions. A Mosaic call
    cannot be partitioned automatically (JAX refuses to lower it), so
    it is mapped by hand: attention is independent per (batch, head),
    so [B, H, T, D] splits its batch over 'dp' and its heads over 'tp'
    with no collective inside. An axis that is absent, or does not
    divide the dimension, leaves that dimension whole."""
    def axis(name, dim):
        n = mesh.axes.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    spec = jax.sharding.PartitionSpec(
        axis("dp", qt.shape[0]), axis("tp", qt.shape[1]), None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal, scale),
        mesh=mesh.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(qt, kt, vt)


def attention_core(q, k, v, causal=True, scale=None, allow_ring=True):
    """GQA-aware attention on [B, T, H, D] tensors — repeats kv heads,
    moves heads next to batch, and dispatches to ring attention (mesh
    has a real 'sp' axis and the caller allows it) or the flash kernel
    (mapped over the mesh by hand unless the caller is already inside
    a shard_map, as the pipeline schedules are).
    Shared by the multihead_attention op and llama_decoder_stack."""
    if k.shape[2] != q.shape[2]:  # GQA repeat kv heads
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))

    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    if (allow_ring and mesh is not None
            and mesh.axes.get("sp", 1) > 1):
        from ..parallel.ring_attention import ring_attention_sharded
        ot = ring_attention_sharded(qt, kt, vt, mesh, axis="sp",
                                    causal=causal)
    elif (mesh is not None
          and not jax.sharding.get_abstract_mesh().manual_axes):
        ot = _mesh_flash_attention(qt, kt, vt, causal, scale, mesh)
    else:
        ot = flash_attention(qt, kt, vt, causal, scale)
    return jnp.transpose(ot, (0, 2, 1, 3))


@register_op("multihead_attention")
def _mha(ctx, ins, attrs):
    """Q,K,V: [B, T, H, D] (K/V may have fewer heads — GQA: repeated to
    match). Dispatch: ring attention when the current mesh has a real
    'sp' axis (long-context sequence parallelism), else the flash kernel.
    """
    return {"Out": [attention_core(ins["Q"][0], ins["K"][0], ins["V"][0],
                                   attrs.get("causal", True),
                                   attrs.get("scale"))]}


@register_op("silu")
def _silu(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [x * jax.nn.sigmoid(x)]}


_STACK_SLOTS = ("AttnNorm", "Wq", "Wk", "Wv", "Wo",
                "MlpNorm", "WGate", "WUp", "WDown")
_MATMUL_SLOTS = ("Wq", "Wk", "Wv", "Wo", "WGate", "WUp", "WDown")
_MOE_SLOTS = ("MoeRouter", "MoeWGate", "MoeWUp", "MoeWDown")
_EXPERT_SLOTS = ("MoeWGate", "MoeWUp", "MoeWDown")


def qmat(x, p, slot, cdt=None):
    """``x @ p[slot]``, int8-serving aware. When the slot carries a
    ``<Slot>Scale`` companion the weight is int8 resident in HBM and the
    matmul runs NATIVELY on the MXU's int8 path: the activation row is
    dynamically quantized (per-row absmax → int8), the dot is
    int8 x int8 -> int32 (``preferred_element_type``), and both scales
    multiply the (tiny) result — W8A8-dynamic, the standard TPU serving
    kernel. Why not dequantize the weight? TPU XLA does not fuse a
    convert into a dot operand, so any ``w.astype(bf16)`` form
    (pre-scaled round 2: 110 tok/s; post-scaled: 125 tok/s, both
    measured on the chip) materializes a full dequantized copy of every
    weight each decode step — 26x slower than the bf16 baseline it was
    supposed to beat. Feeding the MXU int8 directly is what lets the
    halved HBM byte traffic actually show up as speed."""
    w = p[slot]
    sc = p.get(slot + "Scale")
    if sc is None:
        return x @ w
    from .moe import _act_quant          # the ONE activation-quant
    cdt = cdt or x.dtype                 # recipe, shared with W8A8 MoE
    xq, xs = _act_quant(x)
    y32 = jax.lax.dot_general(
        xq, w, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = (y32.astype(jnp.float32) * xs
         * sc.reshape(-1).astype(jnp.float32))
    return y.astype(cdt)


def _reject_quant_scales(ins, op_name):
    """The training-side stack ops must never see int8 ``<Slot>Scale``
    companions: qmat's activation quantization uses ``jnp.round``,
    whose zero gradient would silently kill every gradient through the
    quantized matmuls instead of failing. W8A8 is a serving-only path
    (llama_generate)."""
    scales = sorted(k for k in ins if k.endswith("Scale"))
    if scales:
        raise ValueError(
            f"{op_name} got int8 quantization scale inputs {scales}; "
            "the W8A8 path is serving-only (jnp.round has zero "
            "gradient — training through it would silently produce "
            "zero gradients). Train in bf16/f32 and quantize the "
            "trained scope (models.llama.quantize_generator_weights).")


class BlockKinds:
    """What one decoder block is made of, as data of the model: its kind
    of attention (``gqa`` | ``latent``), of feed-forward (``swiglu`` |
    ``routed``, the latter with a shared expert where the parameters
    hold one) and of residual path (``plain`` | ``mhc``), with the sizes
    each kind needs. Any combination is a block: Xing4.0 is latent +
    routed + mhc, DeepSeek-V3 latent + routed + plain, Llama and Mistral
    gqa + swiglu + plain. ``block_forward`` is the one definition that
    reads it; training, the generator and the paged programs derive from
    that.

    ``latent`` (multi-head latent attention, DeepSeek-V2,
    arXiv:2405.04434): ``kv_rank`` normalised latent + ``rope_dim``
    rotated key a token are the cache's entry; ``nope_dim``/``v_dim``
    are a head's expanded key and value widths; ``rope_inv_freq`` and
    ``softmax_scale`` carry YaRN. ``routed``: ``scoring``,
    ``route_scale``, ``n_group`` and ``topk_group`` as ops/moe.py
    moe_route, over the router's whole width (its parameter's); the
    experts held are the expert parameters' leading dimension, from
    ``experts_first`` on: fewer than the router's width where the block
    is one chip's share of an expert-parallel layer (moe_apply_sorted).
    ``mhc`` (manifold-constrained hyper-connections, arXiv:2512.24880):
    ``n_streams`` residual streams mixed by per-token matrices, the
    stream-to-stream one made doubly stochastic by ``sinkhorn_iters``
    rounds; ``plain`` has one stream and no such parameters.

    ``gqa`` takes a key width ``key_dim`` beside a value width ``v_dim``
    (None / 0: both the query projection's width over the heads), of
    which the first ``rotary_dim`` are rotated (None: all of them), and a
    ``value_scale`` on the values. A stack may MIX KINDS OF ATTENTION
    LAYER: ``attn_kinds`` is then a tuple of dicts, one a kind, each with
    its ``name`` (the ``attn/<name>`` and ``cache/<name>`` scopes of a
    trace), ``n_kv``, rotary ``base``, ``window`` (None: every earlier
    position is seen; w: the query and the w - 1 before it), ``sink``
    (whether the layer's parameters hold a learned scalar a head,
    ``Sink``, that the softmax counts as one more column of its
    denominator), ``stack`` (the prefix of the op's slots that hold this
    kind's stacked layers) and ``pools`` (which of the model's cache
    pools are this kind's: a kind with a window keeps a RING of the last
    positions, a kind without keeps the whole sequence), and
    ``layer_kinds`` names each layer's kind by its index there.
    ``of(k)`` is this object at kind ``k``. A kind's dict may also carry
    its OWN QUERY HEADS and rotation (absent: the model's): ``n_heads``
    (its ``Wq`` / ``Wo`` are then another width than the other kinds'),
    ``rotary_dim``, ``inv_freq`` (the rotated part's inverse frequencies,
    ``yarn_inv_freq``; absent: ``base ** (-2i / rotary_dim)``) and
    ``rope_factor`` (on the cosines and sines). ``gqa`` GATES each head's
    result before ``Wo`` where the layer holds ``Wg`` [D, heads]:
    ``sigmoid(u Wg)`` of the layer's normed input (arXiv:2505.06708).

    A kind of layer may have ANOTHER MIXER than attention: ``"mixer":
    "ssm"`` in its dict is the selective state-space mixer (ops/ssm.py),
    whose cache is no list of positions but ONE entry a sequence, the
    ``state`` cache kind: its ``pools`` are the recurrent state ``[layers,
    entries, N, C]`` float32 and the convolution's tail ``[layers,
    entries, (k - 1) * C]``, reached through a third table, [rows, 1]
    (``StateTable``). ``rotary_dim`` 0: the ``gqa`` kinds rotate nothing
    (the state layers carry the order). ``"mixer": "delta"`` is the third
    mixer, the gated delta rule (ops/delta_rule.py), of the same cache
    kind: its state a MATRIX a head, ``[layers, entries, heads, dk, dv]``
    float32, a prompt computed ``delta_rule.CHUNK`` positions at a time as
    matrix products; its sizes are read off its parameters. ``"mixer":
    "conv"`` is the fourth, the gated short convolution (ops/short_conv.py):
    a tail and NO state, so its kind has ONE pool, ``[layers, entries, (k -
    1) * C]`` (``pools`` names one). ``gqa`` norms its query and key
    projection where the layer holds ``QNorm`` / ``KNorm``: the WHOLE
    projection where the weight is as wide as it, EACH HEAD with the one
    weight where the weight is a head wide (read off the parameter).
    ``route_eps`` is what a sigmoid router adds to the sum of the picked
    scores it divides them by (ops/moe.py ``moe_route``). ``"mixer": "kda"``
    is the fifth, Kimi Delta Attention (arXiv:2510.26692): the delta rule
    again (the same module, cache kind and pools) with a decay A CHANNEL
    (the layer's ``Wa`` is [D, heads * dk], read off its width), a sigmoid
    output gate, and the kind's own ``rule``: a dict of ops/delta_rule.py's
    three data (``scope`` of its spans, ``beta_max`` the write strength's
    ceiling, ``floor`` the lower-bound gate's bound), absent for ``delta``,
    whose values are that module's defaults.

    LATENT ATTENTION MAY BE A KIND AMONG OTHERS: ``"mixer": "latent"`` in a
    kind's dict (its widths the model's ``kv_rank`` / ``rope_dim`` /
    ``nope_dim`` / ``v_dim`` / ``softmax_scale``) with ONE ``sequence`` pool
    ``[its layers, pages, page_size, stored entry]``: its layers fold
    their prefill expanded and decode absorbed through the paths a
    whole-stack latent model has. A layer WITHOUT the query's low-rank
    pair holds ``Wq`` [D, heads * (nope_dim + rope_dim)] in place of
    ``Wqa`` / ``QNorm`` / ``Wqb``, and one that holds ``Wg`` gates each
    head's result as ``gqa`` does.

    A stack may be RUN SEVERAL TIMES A TOKEN (a looped language model,
    arXiv:2510.25741): ``passes`` > 1 runs the same stacked layers that
    many times, every pass with keys and values of its own, so the cache
    is ``passes`` times as deep as the weights (layer ``j`` of pass ``s``
    at cache layer ``s * layers + j``: ``_PagedRunner._stack_forward``),
    and the model's final norm closes every pass. Such a stack is one
    kind of plain, dense layer. The ``plain`` residual has a second form,
    read off the PARAMETERS: where a layer holds ``AttnPostNorm`` /
    ``MlpPostNorm`` the sublayer's output is normed too, ``x +
    norm(f(norm(x)))`` (a norm on each side: sandwich normalisation), and
    where it holds the post-norm and NO ``AttnNorm`` / ``MlpNorm`` the
    sublayer reads the stream as it is, ``x + norm(f(x))`` (the reordered
    norm, OLMo 2, arXiv:2501.00656)."""

    def __init__(self, *, n_heads, n_kv=None, base=10000.0, eps=1e-6,
                 attention="gqa", ffn="swiglu", residual="plain",
                 moe_top_k=2, scoring="softmax", route_scale=1.0,
                 n_group=1, topk_group=1, experts_first=0,
                 kv_rank=0, rope_dim=0, nope_dim=0, v_dim=0,
                 rope_inv_freq=None, softmax_scale=None, n_streams=1,
                 sinkhorn_iters=0, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
                 key_dim=None, rotary_dim=None, value_scale=1.0,
                 attn_kinds=None, layer_kinds=None, passes=1,
                 route_eps=1e-20):
        for kind, table in ((attention, _ATTENTION), (ffn, _FFN),
                            (residual, _RESIDUAL)):
            if kind not in table:
                raise ValueError(f"unknown block kind {kind!r}; have "
                                 f"{sorted(table)}")
        self.n_heads, self.n_kv = n_heads, n_kv or n_heads
        self.base, self.eps = base, eps
        self.attention, self.ffn, self.residual = attention, ffn, residual
        self.moe_top_k, self.scoring = moe_top_k, scoring
        self.route_scale, self.route_eps = route_scale, route_eps
        self.n_group, self.topk_group = n_group, topk_group
        self.experts_first = experts_first
        self.kv_rank, self.rope_dim = kv_rank, rope_dim
        self.nope_dim, self.v_dim = nope_dim, v_dim
        self.rope_inv_freq = rope_inv_freq
        self.softmax_scale = softmax_scale
        self.n_streams, self.sinkhorn_iters = n_streams, sinkhorn_iters
        self.hc_eps, self.hc_clamp = hc_eps, tuple(hc_clamp)
        self.key_dim, self.rotary_dim = key_dim, rotary_dim
        self.value_scale = value_scale
        self.window, self.sink = None, False
        self.name = None            # of(k): the kind's, for its scopes
        self.rotary_inv_freq, self.rotary_factor = None, 1.0
        self.attn_kinds = None if attn_kinds is None \
            else tuple(dict(k) for k in attn_kinds)
        self.layer_kinds = None if layer_kinds is None \
            else tuple(int(k) for k in layer_kinds)
        self.passes = int(passes)
        if self.passes < 1 or (self.passes > 1 and (
                ffn == "routed" or residual != "plain"
                or layer_kinds is not None)):
            raise ValueError(
                f"{passes} passes over a stack of {ffn} / {residual} "
                "layers" + (" of several kinds" if layer_kinds else "")
                + ": a stack run more than once is one kind of plain, "
                "dense layer")

    def of(self, k):
        """These kinds with attention kind ``k``'s own values in place."""
        kind = self.attn_kinds[k]
        out = copy.copy(self)
        out.n_kv, out.base = kind["n_kv"], kind["base"]
        out.window, out.sink = kind["window"], kind["sink"]
        out.attention = kind.get("mixer", self.attention)
        out.name = kind["name"]
        out.n_heads = kind.get("n_heads", self.n_heads)
        out.rotary_dim = kind.get("rotary_dim", self.rotary_dim)
        out.rotary_inv_freq = kind.get("inv_freq")
        out.rotary_factor = kind.get("rope_factor", 1.0)
        return out


def _head_gate(kinds, p, u, a):
    """a [b, t, heads * dv], each head's part times ``sigmoid(u Wg)`` [..,
    heads] of the layer's normed input (arXiv:2505.06708)."""
    b, t, _ = u.shape
    with jax.named_scope("/".join(
            x for x in ("attn", kinds.name, "gate") if x)):
        g = jax.nn.sigmoid(qmat(u, p, "Wg").astype(jnp.float32))
        return (a.reshape(b, t, kinds.n_heads, -1).astype(jnp.float32)
                * g[..., None]).astype(a.dtype).reshape(a.shape)


def _gqa_attention(kinds, p, u, pos, attend_fn):
    """Grouped-query projections, the first ``rotary_dim`` widths of every
    query and key head rotated (all of them where None), the values times
    ``value_scale``; ``attend_fn(q, (k, v))`` owns the attention and any
    cache. ``QNorm`` / ``KNorm`` norm the projection before the rotation,
    whole or a head at a time as the weight's width says. Where the layer
    holds ``Wg`` each head's result is gated by ``sigmoid(u Wg)`` [..,
    heads] before ``Wo``."""
    b, t, _ = u.shape
    hd = kinds.key_dim or p["Wq"].shape[-1] // kinds.n_heads
    rd = kinds.rotary_dim
    rope = functools.partial(apply_rope_at, positions=pos, base=kinds.base,
                             inv_freq=kinds.rotary_inv_freq,
                             factor=kinds.rotary_factor)

    def rotate(x):
        if rd == 0:
            return x
        if rd is None or rd == hd:
            return rope(x)
        return jnp.concatenate([rope(x[..., :rd]), x[..., rd:]], axis=-1)

    def heads(slot, norm, n):
        x = qmat(u, p, slot)
        w = p.get(norm)
        whole = w is not None and w.shape[-1] == x.shape[-1]
        if whole:                       # over the whole projection
            x = rms_normalize(x, w, kinds.eps)
        x = x.reshape(b, t, n, hd)
        if w is not None and not whole:     # a head at a time, one weight
            with jax.named_scope("/".join(
                    s for s in ("attn", kinds.name, "head_norm") if s)):
                x = rms_normalize(x, w, kinds.eps)
        return rotate(x)

    q = heads("Wq", "QNorm", kinds.n_heads)
    k = heads("Wk", "KNorm", kinds.n_kv)
    v = qmat(u, p, "Wv").reshape(b, t, kinds.n_kv, -1)
    if kinds.value_scale != 1.0:
        v = v * jnp.asarray(kinds.value_scale, v.dtype)
    a = attend_fn(q, (k, v))
    if p.get("Wg") is not None:
        a = _head_gate(kinds, p, u, a)
    return qmat(a, p, "Wo")


def _latent_attention(kinds, p, u, pos, attend_fn):
    """Latent attention's projections: the query through its low-rank
    pair, the key/value side down to ONE ``[kv_rank | rope_dim]`` entry a
    token (normalised latent, rotated key shared by all heads), which is
    all a cache holds. ``attend_fn((q_nope, q_pe), (entry,))`` attends,
    expanded or absorbed, and returns [b, t, heads * v_dim]."""
    b, t, _ = u.shape
    r = kinds.kv_rank
    if p.get("Wqa") is None:    # no low-rank pair on the query's side
        q = (u @ p["Wq"]).reshape(b, t, kinds.n_heads, -1)
    else:
        q = (rms_normalize(u @ p["Wqa"], p["QNorm"], kinds.eps)
             @ p["Wqb"]).reshape(b, t, kinds.n_heads, -1)
    q_nope, q_pe = q[..., :kinds.nope_dim], q[..., kinds.nope_dim:]
    ckv = u @ p["Wkva"]
    c = rms_normalize(ckv[..., :r], p["KvNorm"], kinds.eps)

    def rotate(x):      # published pairs are interleaved: de-interleave
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        return apply_rope_at(x, pos, inv_freq=kinds.rope_inv_freq)

    k_pe = rotate(ckv[..., None, r:])[:, :, 0]
    entry = jnp.concatenate([c, k_pe], axis=-1)
    a = attend_fn((q_nope, rotate(q_pe)), (entry,))
    if p.get("Wg") is not None:
        a = _head_gate(kinds, p, u, a)
    return a @ p["Wo"]


# the mixers whose cache is the ``state`` kind, each a module of ``window(p,
# z, state0, tail0, lens, eps)``, ``step(p, z, s_pool, layer, held, tail0,
# eps)`` and ``step_in_kernel(pool_shape, pool_dtype)``; a mixer that keeps
# a tail and no state (``conv``) is handed None for the state and its pool;
# ``window`` and ``step`` also take the keywords a kind's dict names under
# ``rule`` (ops/delta_rule.py's: ``kda`` is that module with a kind's own)
_STATE_MIXERS = {"ssm": ssm, "delta": delta_rule, "conv": short_conv,
                 "kda": delta_rule}


def _state_stats(mixer):
    """The device counters a model with layers of this state mixer adds,
    under the mixer's name: (states its active rows updated over decode
    steps, layers x rows; real positions its prefill windows computed,
    layers x positions)."""
    return (f"{mixer}_state_updates_total",
            f"{mixer}_prefill_positions_total")


def _state_pools(mine):
    """(state pool, tail pool) of a state kind's pools: a mixer with a
    tail and no state (``conv``) has the tail's alone, and None."""
    return (None, mine[0]) if len(mine) == 1 else tuple(mine)


def _is_latent(spec):
    """Whether a kind of layer (an ``attn_kinds`` dict) is latent
    attention: ONE ``sequence`` pool of ``[latent | rotated key]``
    entries."""
    return spec.get("mixer") == "latent"


def _keeps_state(spec):
    """Whether a kind of layer (an ``attn_kinds`` dict) has a cache of the
    ``state`` kind: ONE entry a sequence, which its mixer carries."""
    return spec.get("mixer") in _STATE_MIXERS


def _ssm_mixer(kinds, p, u, pos, attend_fn):
    """The selective state-space mixer's projections: ``[z | g] = W_in u``,
    out ``= W_out (y * silu(g))``. ``attend_fn(z, ())`` owns what lies
    between, the convolution and the recurrence over z (ops/ssm.py), and
    the state a sequence carries from one call to the next."""
    zg = qmat(u, p, "WIn")
    z, g = jnp.split(zg, 2, axis=-1)
    return qmat(attend_fn(z, ()) * jax.nn.silu(g), p, "WOut")


def _delta_mixer(kinds, p, u, pos, attend_fn, gate=jax.nn.silu):
    """The gated delta rule's projections: queries, keys and values (to
    be convolved) and the decay's (a head's, or a channel's where ``Wa`` is
    that wide) and the write strength's a head (not to be), one ``z``; out
    ``= W_o (norm(o) * gate(W_z u))``, the norm an RMSNorm over each head's
    values, ``gate`` SiLU (``delta``) or a sigmoid (``kda``).
    ``attend_fn(z, ())`` owns what lies between (ops/delta_rule.py) and
    the state a sequence carries."""
    z = jnp.concatenate([qmat(u, p, s) for s in
                         ("Wq", "Wk", "Wv", "Wa", "Wb")], axis=-1)
    o = attend_fn(z, ())
    dv = p["GNorm"].shape[-1]
    o = rms_normalize(o.reshape(o.shape[:-1] + (-1, dv)), p["GNorm"],
                      kinds.eps).reshape(o.shape)
    return qmat(o * gate(qmat(u, p, "Wz")), p, "Wo")


def _conv_mixer(kinds, p, u, pos, attend_fn):
    """The gated short convolution's projections: ``[B | C | z] = W_in u``,
    the input gated BEFORE the taps (``g = B * z``) and their result
    after (out ``= W_out (C * c)``). ``attend_fn(g, ())`` owns what lies
    between, the taps over g (ops/short_conv.py), and the tail a sequence
    carries from one call to the next."""
    with jax.named_scope("mixer/conv/in"):
        gate_in, gate_out, z = jnp.split(qmat(u, p, "WIn"), 3, axis=-1)
        g = gate_in * z
    c = attend_fn(g, ())
    with jax.named_scope("mixer/conv/out"):
        return qmat(gate_out * c, p, "WOut")


def _swiglu(p, x, gate="WGate", up="WUp", down="WDown"):
    g = qmat(x, p, gate)
    return qmat((g * jax.nn.sigmoid(g)) * qmat(x, p, up), p, down)


def _swiglu_ffn(kinds, p, u, valid):
    return _swiglu(p, u), None


def _routed_ffn(kinds, p, u, valid):
    """Drop-free routed experts (ops/moe.py), plus the shared expert
    where ``p`` holds one. Also returns (the held experts' load in this
    call, [E] int32 over the ``valid`` tokens; the experts picked over
    the router's whole width, [b, t, K])."""
    from . import moe
    b, t, d = u.shape
    xt = u.reshape(b * t, d)
    if p.get("MoeWGateScale") is not None:          # W8A8 expert stacks
        return moe.moe_apply_no_drop_q(
            xt, p["MoeRouter"], p["MoeWGate"], p["MoeWUp"], p["MoeWDown"],
            {"gate": p["MoeWGateScale"], "up": p["MoeWUpScale"],
             "down": p["MoeWDownScale"]},
            kinds.moe_top_k).reshape(b, t, d), None
    n_held, width = p["MoeWGate"].shape[-3], p["MoeRouter"].shape[-1]
    # a share of the layer: fewer experts than the router is wide
    held = None if n_held == width else (kinds.experts_first, width)
    with jax.named_scope("moe/route"):
        idx, gates = moe.moe_route(
            xt, p["MoeRouter"], kinds.moe_top_k, kinds.scoring,
            p.get("MoeBias"), kinds.route_scale, kinds.n_group,
            kinds.topk_group, kinds.route_eps)
        load = moe.moe_load(idx, n_held,
                            None if valid is None else valid.reshape(-1),
                            kinds.experts_first)
    with jax.named_scope("moe/experts"):
        # p["ExpertsOf"]: the expert stacks are the whole model's and
        # this is the layer to take (_PagedRunner._stack_forward)
        out = moe.moe_apply_sorted(xt, idx, gates, p["MoeWGate"],
                                   p["MoeWUp"], p["MoeWDown"],
                                   layer=p.get("ExpertsOf"), held=held)
    if p.get("ShWGate") is not None:
        with jax.named_scope("moe/shared"):
            out = out + _swiglu(p, xt, "ShWGate", "ShWUp", "ShWDown")
    return out.reshape(b, t, d), (load, idx.reshape(b, t, -1))


def _plain_residual(kinds, p, which, x, sublayer):
    pre, post = p.get(which + "Norm"), p.get(which + "PostNorm")
    y = sublayer(x if pre is None     # the reordered norm: behind alone
                 else rms_normalize(x, pre, kinds.eps))
    if post is not None:        # a norm on each side of the sublayer
        y = rms_normalize(y, post, kinds.eps)
    return x + y


def sinkhorn_knopp(m, iters, eps):
    """``iters`` rounds of row then column normalisation of the positive
    matrices m [..., n, n]: towards doubly stochastic."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _mhc_residual(kinds, p, which, x, sublayer):
    """x [b, t, n, D]: the sublayer reads ``Hpre x``, its output goes
    back through ``Hpost`` and the streams are mixed by ``Hres``; all
    three are functions of the token's normalised streams, computed in
    float32 at "highest" precision."""
    b, t, n, d = x.shape
    hi = jax.lax.Precision.HIGHEST
    with jax.named_scope("mhc/mix"):
        xf = x.astype(jnp.float32)
        z = jnp.dot(rms_normalize(xf.reshape(b, t, n * d), None, kinds.eps),
                    p["Hc" + which + "Phi"], precision=hi)
        alpha, bias = p["Hc" + which + "Alpha"], p["Hc" + which + "Bias"]
        pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n]
                                    + bias[n:2 * n])
        res = alpha[2] * z[..., 2 * n:] + bias[2 * n:]
        res = sinkhorn_knopp(
            jnp.exp(jnp.clip(res, *kinds.hc_clamp)).reshape(b, t, n, n),
            kinds.sinkhorn_iters, kinds.hc_eps)
        u = jnp.einsum("btn,btnd->btd", pre, xf,
                       precision=hi).astype(x.dtype)
    y = sublayer(rms_normalize(u, p[which + "Norm"], kinds.eps))
    with jax.named_scope("mhc/mix"):
        out = jnp.einsum("btij,btjd->btid", res, xf, precision=hi) \
            + post[..., None] * y.astype(jnp.float32)[:, :, None, :]
    return out.astype(x.dtype)


_ATTENTION = {"gqa": _gqa_attention, "latent": _latent_attention,
              "ssm": _ssm_mixer, "delta": _delta_mixer,
              "conv": _conv_mixer,
              "kda": functools.partial(_delta_mixer, gate=jax.nn.sigmoid)}
_FFN = {"swiglu": _swiglu_ffn, "routed": _routed_ffn}
_RESIDUAL = {"plain": _plain_residual, "mhc": _mhc_residual}


def block_forward(kinds, p, x, pos, attend_fn, ffn=None, valid=None):
    """One decoder block, the single copy of the block math: residual(
    attention) then residual(feed-forward), each kind looked up in
    ``kinds``. ``ffn`` overrides the feed-forward kind for this layer (a
    model's leading dense layers); ``attend_fn(q, entries) -> out`` gets
    the positioned queries and the token's cache entries and owns the
    attention and any cache side effects. Returns (x, routing): a routed
    layer's (per-expert load over the ``valid`` tokens, experts picked
    [b, t, K]), else None."""
    residual = _RESIDUAL[kinds.residual]
    routing = []

    def feed_forward(u):
        out, info = _FFN[ffn or kinds.ffn](kinds, p, u, valid)
        routing.append(info)
        return out

    x = residual(kinds, p, "Attn", x, lambda u: _ATTENTION[
        kinds.attention](kinds, p, u, pos, attend_fn))
    x = residual(kinds, p, "Mlp", x, feed_forward)
    return x, routing[0]


def decoder_block(p, h, *, n_heads, n_kv, base, eps, pos, attend_fn,
                  moe_top_k=2):
    """One Llama decoder block — block_forward at the Llama kinds, shared
    by training (llama_decoder_stack) and generation (llama_generate):
    rms_norm → roped QKV at ``pos`` → ``attend_fn`` → residual →
    rms_norm → SwiGLU (or, where ``p`` holds a router, the drop-free
    routed form: the capacity-competition of the training form would
    make cached decode depend on the rest of the batch) → residual.

    attend_fn(q, k, v) -> [b, t, n_heads*hd] gets the roped q/k and raw
    v ([b, t, heads, hd]) and owns the attention (and any KV-cache
    side effects)."""
    kinds = BlockKinds(
        n_heads=n_heads, n_kv=n_kv, base=base, eps=eps,
        moe_top_k=moe_top_k,
        ffn="swiglu" if p.get("MoeRouter") is None else "routed")
    return block_forward(kinds, p, h, pos,
                         lambda q, kv: attend_fn(q, *kv))[0]


def make_flash_block(n_heads, n_kv, base, eps, remat=True):
    """The training-side decoder block (flash attention, causal),
    optionally rematerialized in backward — the activation-memory
    policy the reference's memory_optimization transpiler
    approximates. allow_ring=False: inside the pipeline shard_map only
    pp/dp axes are mapped, so the sp ring collective is unavailable
    (and build_llama rejects shard_pp + shard_sp accordingly)."""
    def block(p, h):
        b, t, _ = h.shape

        def attend(q, k, v):
            return attention_core(q, k, v, causal=True,
                                  allow_ring=False).reshape(b, t, -1)

        return decoder_block(p, h, n_heads=n_heads, n_kv=n_kv,
                             base=base, eps=eps, pos=jnp.arange(t),
                             attend_fn=attend)

    return jax.checkpoint(block) if remat else block


@register_op("llama_stack_1f1b_loss")
def _llama_stack_1f1b_loss(ctx, ins, attrs):
    """The decoder stack PLUS final norm, lm head and cross entropy as
    one loss-valued op, so the 1F1B schedule can run backward inside
    its own forward: on a 'pp' mesh the op executes
    :func:`paddle_tpu.parallel.pipeline.one_f_one_b` (interleaved
    fwd/bwd, ≤n_stages in-flight activations, grads accumulated
    in-schedule) and exposes those grads to the program's autodiff
    through a ``custom_vjp`` that scales them by the incoming loss
    cotangent — exact because the output is the scalar loss itself.
    Off-mesh it is a plain scan + loss (ordinary AD applies).

    X [B, T, D] embedded tokens; Targets [B, T] int; Loss [] scalar
    mean cross entropy.
    """
    x = ins["X"][0]
    tgt = ins["Targets"][0]
    _reject_quant_scales(ins, "llama_stack_1f1b_loss")
    params = {s: ins[s][0] for s in _STACK_SLOTS}
    fnorm = ins["FinalNorm"][0]
    head = ins["LmHead"][0]
    n_heads = attrs["n_heads"]
    n_kv = attrs.get("n_kv_heads", n_heads)
    base = attrs.get("rope_base", 10000.0)
    eps = attrs.get("epsilon", 1e-6)
    n_micro = attrs.get("n_micro", 0)
    blk = make_flash_block(n_heads, n_kv, base, eps,
                           attrs.get("remat", True))

    # vocab-chunked loss (ops/fused_loss.py) — at 128k vocab the naive
    # [mb*T, vocab] logits would be materialized per microbatch AND
    # held as a vjp residual for the in-schedule backward
    v = head.shape[1]
    loss_chunk = min(int(attrs.get("loss_chunk", 8192) or 8192), v)

    def ce_loss(lp, y, t):
        from .fused_loss import _fused_ce
        h2 = rms_normalize(y, lp["fnorm"], eps)
        h2 = h2.reshape(-1, h2.shape[-1])
        losses = _fused_ce(h2, lp["head"], t.reshape(-1).astype(
            jnp.int32), loss_chunk, v, -100)
        return jnp.mean(losses)

    lp = {"fnorm": fnorm, "head": head}

    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    pp = mesh.axes.get("pp", 1) if mesh is not None else 1
    n_layers = params["Wq"].shape[0]
    if pp <= 1:
        out, _ = jax.lax.scan(
            lambda h, p: (blk(p, h), None), x, params,
            unroll=max(1, int(attrs.get("scan_unroll", 1))))
        return {"Loss": [ce_loss(lp, out, tgt)]}

    if n_layers % pp:
        raise ValueError(
            f"llama_stack_1f1b_loss: {n_layers} layers do not split "
            f"over the mesh 'pp' axis of size {pp}")
    from ..parallel.pipeline import one_f_one_b
    per_stage = n_layers // pp
    nm = int(n_micro) or pp
    b = x.shape[0]
    if b % nm:
        raise ValueError(
            f"llama_stack_1f1b_loss: batch {b} is not divisible by "
            f"n_micro={nm} microbatches")
    dp = mesh.axes.get("dp", 1)
    if (b // nm) % dp:
        raise ValueError(
            f"llama_stack_1f1b_loss: microbatch {b // nm} is not "
            f"divisible by the mesh 'dp' axis of size {dp}")

    def stage_fn(sp, h):
        return jax.lax.scan(lambda c, p: (blk(p, c), None), h, sp)[0]

    run = one_f_one_b(stage_fn, ce_loss, mesh, loss_params=True,
                      return_dx=True)

    @jax.custom_vjp
    def pipe_loss(params_l, lp, x_full, tgt_full):
        return _pipe_fwd(params_l, lp, x_full, tgt_full)[0]

    def _pipe_fwd(params_l, lp, x_full, tgt_full):
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((pp, per_stage) + a.shape[1:]),
            params_l)
        micro_x = x_full.reshape((nm, b // nm) + x_full.shape[1:])
        micro_y = tgt_full.reshape((nm, b // nm) + tgt_full.shape[1:])
        loss, grads, lgrads, dx = run(stacked, lp, micro_x, micro_y)
        grads_l = jax.tree_util.tree_map(
            lambda g, a: g.reshape(a.shape), grads, params_l)
        dx_full = dx.reshape(x_full.shape)
        return loss, (grads_l, lgrads, dx_full)

    def _pipe_bwd(res, ct):
        grads_l, lgrads, dx_full = res
        scale = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: (a * ct).astype(a.dtype), t)
        t_tan = np.zeros(tgt.shape, jax.dtypes.float0)
        return scale(grads_l), scale(lgrads), scale(dx_full), t_tan

    pipe_loss.defvjp(lambda p_, l_, x_, t_: _pipe_fwd(p_, l_, x_, t_),
                     _pipe_bwd)
    return {"Loss": [pipe_loss(params, lp, x, tgt)]}


@register_op("llama_generate", stateful=True)
def _llama_generate(ctx, ins, attrs):
    """Greedy autoregressive generation with a KV cache, as ONE XLA
    program: a prefill pass over the prompt (full causal attention,
    writing every layer's K/V), then a ``lax.scan`` over
    ``max_new_tokens`` single-position decode steps that read/extend
    the cache. Weights are the same layer-stacked tensors (plus
    embedding / final norm / lm head) the training-side
    ``llama_decoder_stack`` uses, so a trained scope generates
    directly. The reference era served decoding through per-op
    interpreter loops (beam_search/while); this is the TPU-first form —
    no host round trip per token.

    Tokens [B, T_prompt] int; Out [B, T_prompt + max_new_tokens].
    """
    tokens = ins["Tokens"][0]
    emb_w = ins["Emb"][0]                               # [V, D]
    params = {s: ins[s][0] for s in _STACK_SLOTS if s in ins}
    for s in _MOE_SLOTS:
        if s in ins:
            params[s] = ins[s][0]
    # int8 scale companions (dense matmul stacks + MoE expert stacks;
    # MoeRouter stays float so it never gets one)
    for s in _MATMUL_SLOTS + ("MoeWGate", "MoeWUp", "MoeWDown"):
        if s + "Scale" in ins:
            params[s + "Scale"] = ins[s + "Scale"][0]
    head_scale = (ins["LmHeadScale"][0] if "LmHeadScale" in ins
                  else None)
    fnorm = ins["FinalNorm"][0]                         # [D]
    head = ins["LmHead"][0]                             # [D, V]
    n_heads = attrs["n_heads"]
    n_kv = attrs.get("n_kv_heads", n_heads)
    base = attrs.get("rope_base", 10000.0)
    eps = attrs.get("epsilon", 1e-6)
    max_new = attrs["max_new_tokens"]
    moe_top_k = int(attrs.get("moe_top_k", 2))
    eos_id = attrs.get("eos_id", -1)
    if eos_id is None:
        eos_id = -1
    eos_id = int(eos_id)
    pad_id = int(attrs.get("pad_id", 0) or 0)
    temperature = float(attrs.get("temperature", 0.0))
    top_k = min(int(attrs.get("top_k", 0)), emb_w.shape[0])
    top_p = float(attrs.get("top_p", 1.0))
    base_key = ctx.next_key()

    b, t_prompt = tokens.shape
    total = t_prompt + max_new

    # Every lax.scan iteration costs loop overhead, and an L-layer inner
    # scan bills it L times to EVERY decoded token. unroll_layers
    # replicates the (small) block body L times instead — one loop
    # level total (the token scan) — and decode_unroll>1 further
    # replicates the token-step body to amortize the outer loop the
    # same way. Both trade compile time for iteration overhead. What an
    # iteration costs is not re-measured on this installation.
    unroll_layers = bool(attrs.get("unroll_layers", False))
    decode_unroll = max(1, int(attrs.get("decode_unroll", 1)))
    kv_int8 = bool(attrs.get("kv_int8", False))

    run_all_layers, _, k_cache0, v_cache0 = _make_cached_runner(
        params, emb_w, fnorm, head, n_heads=n_heads, n_kv=n_kv,
        base=base, eps=eps, b=b, total=total,
        unroll_layers=unroll_layers, moe_top_k=moe_top_k,
        kv_int8=kv_int8)

    def logits_of(h_last):
        hn = rms_normalize(h_last, fnorm, eps)
        if head_scale is None:
            return (hn @ head).astype(jnp.float32)
        # int8 head: same native W8A8 matmul as the block (qmat)
        return qmat(hn, {"W": head, "WScale": head_scale}, "W",
                    cdt=jnp.float32)

    def pick(logits, step):
        """Next-token choice: greedy at temperature 0, else sampled
        with optional top-k truncation and top-p (nucleus) filtering."""
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = warp_logits(logits, temperature, top_k, top_p)
        key = jax.random.fold_in(base_key, step)
        return jax.random.categorical(key, logits, axis=-1)

    # ---- prefill over the prompt -------------------------------------
    h = emb_w[tokens]                                   # [b, T, D]
    h, k_cache, v_cache = run_all_layers(h, k_cache0, v_cache0, 0,
                                         t_prompt)
    first_logits = logits_of(h[:, -1])                  # [b, V] f32
    first_new = pick(first_logits, jnp.int32(0))        # [b]

    # ---- decode scan: max_new - 1 steps, each emitting the NEXT
    # token (the last new token needs no further forward pass).
    # Sequences that have emitted eos_id keep emitting pad_id — the
    # static XLA loop cannot exit early, so finished rows are masked
    # (the HF generate convention, tests/test_llama_hf_parity.py) ----
    def decode(carry, _):
        tok, done, pos, k_cache, v_cache = carry
        x = emb_w[tok][:, None, :]                      # [b, 1, D]
        x, k_cache, v_cache = run_all_layers(x, k_cache, v_cache,
                                             pos, 1)
        nxt = pick(logits_of(x[:, 0]), pos)
        if eos_id >= 0:
            nxt = jnp.where(done, jnp.asarray(pad_id, nxt.dtype), nxt)
            done = done | (nxt == eos_id)
        return (nxt, done, pos + 1, k_cache, v_cache), nxt

    done0 = (first_new == eos_id) if eos_id >= 0 else jnp.zeros(
        (b,), bool)
    (_, _, _, _, _), toks = jax.lax.scan(
        decode, (first_new, done0, jnp.int32(t_prompt), k_cache,
                 v_cache), None, length=max_new - 1,
        unroll=min(decode_unroll, max(1, max_new - 1)))
    rest = jnp.moveaxis(toks, 0, 1)             # [b, max_new - 1]
    out = jnp.concatenate(
        [tokens, first_new[:, None].astype(tokens.dtype),
         rest.astype(tokens.dtype)], axis=1)
    outs = {"Out": [out]}
    if attrs.get("return_probs", False):
        # first decode step's full next-token distribution, computed
        # entirely from the prefill KV cache — the quality instrument
        # quantized-cache variants (kv_int8) are pinned against at the
        # probability level, not just via token agreement
        outs["FirstProbs"] = [jax.nn.softmax(first_logits, axis=-1)]
    return outs


def _make_cached_runner(params, emb_w, fnorm, head, *, n_heads, n_kv,
                        base, eps, b, total, unroll_layers=False,
                        moe_top_k=2, kv_int8=False):
    """KV-cached model runner shared by llama_generate and
    llama_spec_generate: returns (run_layers, logits_all, k_cache0,
    v_cache0) closures over one model's stacked weights. int8
    ``<Slot>Scale`` companions and MoE slots work IF the caller
    assembles them into ``params`` (llama_generate does; the spec op
    is float-only and guards against int8 scopes). The attention is
    the grouped-einsum GQA against the small n_kv cache (never
    expanded to n_heads — that expansion would cost rep x the
    bandwidth the small cache exists to save), with
    write-before-attend dynamic_update_slice cache updates."""
    from .moe import _act_quant        # the ONE activation-quant recipe
    n_layers = params["Wq"].shape[0]
    hd = params["Wq"].shape[-1] // n_heads
    rep = n_heads // n_kv

    def kv_quant(t):
        """Per-(position, kv-head) absmax int8 quantization of a K/V
        block [b, t, g, hd] — the scale rides along the cache as a
        separate pytree leaf."""
        q, s = _act_quant(t)
        return q, s[..., 0]                       # scale [b, t, g]

    def cached_attend(q, k_cache, v_cache, q_pos0, t_len):
        qg = q.reshape(b, t_len, n_kv, rep, hd)
        q_pos = q_pos0 + jnp.arange(t_len)[:, None]
        k_pos = jnp.arange(total)[None, :]
        mask = k_pos <= q_pos
        if kv_int8:
            # int8 KV serving: the cache streams at 1 byte/element and
            # BOTH attention contractions run natively int8 (the W8A8
            # lesson — TPU XLA does not fuse a convert into a dot
            # operand, so a dequantize-on-read form would materialize
            # a full-width cache copy every step and lose the saving).
            # QK^T: per-query-row-quantized q x int8 K; both scales
            # factor out per output element. Scores*V: the per-position
            # V scale sits INSIDE the contraction, so it folds into the
            # f32 softmax weights BEFORE their row quantization.
            kq, ks = k_cache["q"], k_cache["s"]
            qq, qs = _act_quant(qg)               # qs [b,q,g,r,1]
            l32 = jnp.einsum("bqgrd,bkgd->bgrqk", qq, kq,
                             preferred_element_type=jnp.int32)
            logits = (l32.astype(jnp.float32)
                      * jnp.moveaxis(qs, (1, 2, 3), (3, 1, 2))
                      * ks.transpose(0, 2, 1)[:, :, None, None, :]
                      / np.sqrt(hd))
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            vq, vs = v_cache["q"], v_cache["s"]
            wf = w * vs.transpose(0, 2, 1)[:, :, None, None, :]
            wq8, wsc = _act_quant(wf)             # rows over k
            o32 = jnp.einsum("bgrqk,bkgd->bqgrd", wq8, vq,
                             preferred_element_type=jnp.int32)
            out = o32.astype(jnp.float32) \
                * jnp.moveaxis(wsc, (1, 2, 3), (2, 3, 1))
        else:
            logits = jnp.einsum("bqgrd,bkgd->bgrqk",
                                qg.astype(jnp.float32),
                                k_cache.astype(jnp.float32)) \
                / np.sqrt(hd)
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                             v_cache.astype(jnp.float32))
        return out.astype(q.dtype).reshape(b, t_len, n_heads * hd)

    def block_step(p, h, kc, vc, t0, t_len):
        caches = {}

        def attend(q, k, v):
            if kv_int8:
                k8, ks = kv_quant(k)
                v8, vs = kv_quant(v)
                caches["k"] = {
                    "q": jax.lax.dynamic_update_slice(
                        kc["q"], k8, (0, t0, 0, 0)),
                    "s": jax.lax.dynamic_update_slice(
                        kc["s"], ks, (0, t0, 0))}
                caches["v"] = {
                    "q": jax.lax.dynamic_update_slice(
                        vc["q"], v8, (0, t0, 0, 0)),
                    "s": jax.lax.dynamic_update_slice(
                        vc["s"], vs, (0, t0, 0))}
            else:
                caches["k"] = jax.lax.dynamic_update_slice(
                    kc, k, (0, t0, 0, 0))
                caches["v"] = jax.lax.dynamic_update_slice(
                    vc, v, (0, t0, 0, 0))
            return cached_attend(q, caches["k"], caches["v"], t0, t_len)

        h = decoder_block(p, h, n_heads=n_heads, n_kv=n_kv, base=base,
                          eps=eps, pos=t0 + jnp.arange(t_len),
                          attend_fn=attend, moe_top_k=moe_top_k)
        return h, caches["k"], caches["v"]

    def run_layers(h, k_caches, v_caches, t0, t_len):
        def layer(carry, xs):
            h = carry
            p, kc, vc = xs
            h, kc, vc = block_step(p, h, kc, vc, t0, t_len)
            return h, (kc, vc)
        h, (k_caches, v_caches) = jax.lax.scan(
            layer, h, (params, k_caches, v_caches),
            unroll=n_layers if unroll_layers else 1)
        return h, k_caches, v_caches

    def logits_all(h):
        """Logits at EVERY position of h [b, t, d] (the verify pass
        scores all candidate positions in one forward)."""
        hn = rms_normalize(h, fnorm, eps)
        return (hn @ head).astype(jnp.float32)

    dt = emb_w.dtype
    if kv_int8:
        k0 = {"q": jnp.zeros((n_layers, b, total, n_kv, hd), jnp.int8),
              "s": jnp.zeros((n_layers, b, total, n_kv), jnp.float32)}
        return run_layers, logits_all, k0, jax.tree.map(jnp.copy, k0)
    k0 = jnp.zeros((n_layers, b, total, n_kv, hd), dt)
    return run_layers, logits_all, k0, jnp.zeros_like(k0)


@register_op("llama_spec_generate", stateful=True)   # rng iff temp > 0
def _llama_spec_generate(ctx, ins, attrs):
    """Speculative decoding as ONE XLA program: a small DRAFT model
    proposes ``gamma`` tokens autoregressively, the TARGET model
    scores all of them (plus a bonus position) in a single cached
    forward, and the longest accepted prefix is kept.

    Two modes share the machinery:

    - **greedy** (temperature 0, rng-free): a draft token is accepted
      iff it equals the target's argmax; every emitted token is the
      target's argmax at its position, so the output is provably
      IDENTICAL to target-only greedy decoding (pinned by test against
      llama_generate).
    - **sampled** (temperature > 0): speculative SAMPLING (the
      rejection-resampling scheme of Leviathan et al. 2022 /
      Chen et al. 2023): the draft SAMPLES x_j ~ q_j from its warped
      distribution, the target computes its warped distribution p_j at
      every candidate position, x_j is accepted with probability
      min(1, p_j(x_j)/q_j(x_j)); the first rejection is replaced by a
      sample from the residual distribution norm(max(p_j - q_j, 0)),
      and a fully-accepted round samples a bonus token from
      p_gamma. Each emitted token is distributed EXACTLY as the warped
      target distribution (temperature/top-k/top-p applied identically
      to both models via warp_logits), so spec sampling ≡ plain
      llama_generate sampling in distribution — pinned statistically
      by test. Unlike greedy it is not bitwise-reproducible against
      llama_generate (different rng consumption), which is inherent to
      the algorithm, not a batching artifact.

    Batch rows advance in LOCKSTEP at the minimum per-row acceptance:
    rows that accepted further simply re-speculate those positions
    next round (greedy: re-verification is deterministic and exact;
    sampled: the continuation is re-drawn, which preserves the target
    distribution by the Markov property — the kept prefix fully
    determines the conditional law of what follows).

    The reference era has no speculative path (its decoding is per-op
    beam_search/while loops); this is a beyond-parity serving feature
    in the TPU-first form: two KV caches, a bounded lax.while_loop
    whose trip count adapts to the measured acceptance, no host round
    trips.
    """
    tokens = ins["Tokens"][0]
    t_params = {s: ins[s][0] for s in _STACK_SLOTS}
    d_params = {s: ins["Draft" + s][0] for s in _STACK_SLOTS}
    emb_w, fnorm, head = (ins["Emb"][0], ins["FinalNorm"][0],
                          ins["LmHead"][0])
    demb, dfnorm, dhead = (ins["DraftEmb"][0], ins["DraftFinalNorm"][0],
                           ins["DraftLmHead"][0])
    for nm, v in [("target", t_params["Wq"]), ("draft", d_params["Wq"]),
                  ("lm_head", head)]:
        if v.dtype == jnp.int8:
            raise NotImplementedError(
                f"llama_spec_generate is float-only but the {nm} "
                "weights in the scope are int8 (a "
                "quantize_generator_weights'd scope?): the op declares "
                "no <Slot>Scale inputs, so int8 arrays would flow into "
                "float matmuls as garbage. Serve quantized models "
                "through build_llama_generator(quantize=True).")
    n_heads = attrs["n_heads"]
    n_kv = attrs.get("n_kv_heads", n_heads)
    d_heads = attrs["draft_n_heads"]
    d_kv = attrs.get("draft_n_kv_heads", d_heads)
    base = attrs.get("rope_base", 10000.0)
    eps = attrs.get("epsilon", 1e-6)
    # the draft keeps ITS OWN rope/eps — serving it under the target's
    # rope_base would silently wreck its proposals (and the speedup)
    d_base = attrs.get("draft_rope_base", base)
    d_eps = attrs.get("draft_epsilon", eps)
    unroll_layers = bool(attrs.get("unroll_layers", False))
    max_new = int(attrs["max_new_tokens"])
    gamma = int(attrs.get("gamma", 4))
    eos_id = attrs.get("eos_id", -1)
    eos_id = -1 if eos_id is None else int(eos_id)
    pad_id = int(attrs.get("pad_id", 0) or 0)
    temperature = float(attrs.get("temperature", 0.0))
    top_k = min(int(attrs.get("top_k", 0)), emb_w.shape[0])
    top_p = float(attrs.get("top_p", 1.0))
    sampled = temperature > 0.0
    # greedy consumes NO rng (the key counter advancing would change
    # the rng stream of every later op in the program vs round 4)
    base_key = ctx.next_key() if sampled else None

    def warp(logits):
        return warp_logits(logits, temperature, top_k, top_p)

    b, t_prompt = tokens.shape
    # room for the largest possible overshoot: the final round may
    # write gamma+1 tokens starting one short of max_new
    total = t_prompt + max_new + gamma + 1

    t_run, t_logits, tk0, tv0 = _make_cached_runner(
        t_params, emb_w, fnorm, head, n_heads=n_heads, n_kv=n_kv,
        base=base, eps=eps, b=b, total=total,
        unroll_layers=unroll_layers)
    d_run, d_logits, dk0, dv0 = _make_cached_runner(
        d_params, demb, dfnorm, dhead, n_heads=d_heads, n_kv=d_kv,
        base=d_base, eps=d_eps, b=b, total=total,
        unroll_layers=unroll_layers)

    # ---- prefill both models over the prompt -------------------------
    th, tk, tv = t_run(emb_w[tokens], tk0, tv0, 0, t_prompt)
    first_logits = t_logits(th[:, -1:])[:, 0]
    if sampled:
        first = jax.random.categorical(
            jax.random.fold_in(base_key, 0), warp(first_logits), axis=-1)
    else:
        first = jnp.argmax(first_logits, axis=-1)             # [b]
    dh, dk, dv = d_run(demb[tokens], dk0, dv0, 0, t_prompt)

    buf0 = jnp.zeros((b, total), tokens.dtype)
    buf0 = jax.lax.dynamic_update_slice(buf0, tokens, (0, 0))
    buf0 = jax.lax.dynamic_update_slice(
        buf0, first[:, None].astype(tokens.dtype), (0, t_prompt))

    def cond(state):
        return state[1] < max_new

    def body(state, round_idx):
        buf, emitted, cur, prev, pos, done, tk, tv, dk, dv = state
        # pos = absolute position of cur (last accepted, unprocessed by
        # the draft; the target processes it as its window's first
        # token). prev = the token at pos-1. round_idx is the outer
        # loop's round counter (sampled mode folds it into the rng at
        # +1 so round keys never collide with the prefill's fold 0).
        kr = (jax.random.fold_in(base_key, round_idx + 1)
              if sampled else None)

        # 1. draft proposes gamma tokens autoregressively (argmax in
        # greedy mode; sampled from its warped distribution q_j in
        # sampled mode, keeping q_j for the acceptance test). The FIRST
        # step processes a 2-token window [prev, cur]: when the prior
        # round accepted all gamma drafts, the draft never processed
        # its own last proposal, leaving a cache hole at pos-1 that
        # later queries would attend as zeros — reprocessing prev is
        # idempotent when no hole exists (same token, same position)
        # and fills it when one does.
        drafts, qs = [], []
        dkc, dvc = dk, dv
        hx, dkc, dvc = d_run(demb[jnp.stack([prev, cur], axis=1)],
                             dkc, dvc, pos - 1, 2)
        dl = d_logits(hx[:, 1:])[:, 0]
        for i in range(gamma):
            if i > 0:
                hx, dkc, dvc = d_run(demb[d_tok][:, None], dkc, dvc,
                                     pos + i, 1)
                dl = d_logits(hx)[:, 0]
            if sampled:
                dl = warp(dl)
                d_tok = jax.random.categorical(
                    jax.random.fold_in(kr, i), dl, axis=-1)
                qs.append(jax.nn.softmax(dl, axis=-1))
            else:
                d_tok = jnp.argmax(dl, axis=-1)
            drafts.append(d_tok)
        D = jnp.stack(drafts, axis=1)                   # [b, gamma]

        # 2. target scores cur + all gamma drafts in ONE forward
        cand = jnp.concatenate(
            [cur[:, None], D.astype(cur.dtype)], axis=1)  # [b, g+1]
        hx, tk, tv = t_run(emb_w[cand], tk, tv, pos, gamma + 1)
        tl = t_logits(hx)                               # [b, g+1, V]

        if sampled:
            # speculative sampling: accept x_j ~ q_j with probability
            # min(1, p_j(x_j)/q_j(x_j)); first rejection resamples from
            # the residual norm(max(p_j - q_j, 0)); a fully-accepted
            # round samples the bonus from p_gamma. Every kept token is
            # then an exact draw from the warped target distribution.
            tl = warp(tl)
            P = jax.nn.softmax(tl, axis=-1)             # [b, g+1, V]
            Q = jnp.stack(qs, axis=1)                   # [b, gamma, V]
            p_d = jnp.take_along_axis(
                P[:, :gamma], D[..., None], axis=-1)[..., 0]
            q_d = jnp.take_along_axis(Q, D[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(jax.random.fold_in(kr, gamma),
                                   (b, gamma))
            accept = u * q_d < p_d                      # u < p/q; q_d>0
            R = jnp.maximum(P[:, :gamma] - Q, 0.0)
            rs = jnp.sum(R, axis=-1, keepdims=True)
            # p == q ⇒ zero residual mass, but rejection there has
            # probability 0 — the fallback to P only keeps the (never
            # kept) sample finite for XLA's unconditional evaluation
            R = jnp.where(rs > 0, R / jnp.maximum(rs, 1e-20),
                          P[:, :gamma])
            res = jax.random.categorical(
                jax.random.fold_in(kr, gamma + 1),
                jnp.log(jnp.maximum(R, 1e-30)), axis=-1)  # [b, gamma]
            bonus = jax.random.categorical(
                jax.random.fold_in(kr, gamma + 2), tl[:, gamma],
                axis=-1)                                # [b]
            a_row = jnp.sum(jnp.cumprod(accept.astype(jnp.int32),
                                        axis=1), axis=1)
            col = jnp.arange(gamma)[None, :]
            # column j < a_row: the accepted draft; j == a_row: the
            # residual resample (bonus at column gamma — only ever
            # kept when every row fully accepted). Columns beyond the
            # kept prefix are overwritten next round before any read.
            body_cols = jnp.where(col < a_row[:, None], D, res)
            raw = jnp.concatenate(
                [body_cols, bonus[:, None]], axis=1)    # [b, g+1]
        else:
            G = jnp.argmax(tl, axis=-1)                 # [b, gamma+1]
            raw = G

        # 3. emission window. Without eos it is raw verbatim; with eos,
        # replay llama_generate's sequential rule over the window (emit
        # pad once done; a row's post-eos cache/logits divergence from
        # the target-only path is unobservable BECAUSE every later
        # emission is pad by the sticky done flag).
        if eos_id >= 0:
            emits, dones = [], []
            dj = done
            for j in range(gamma + 1):
                e = jnp.where(dj, jnp.asarray(pad_id, raw.dtype),
                              raw[:, j])
                dj = dj | (e == eos_id)
                emits.append(e)
                dones.append(dj)
            E = jnp.stack(emits, axis=1)                # [b, gamma+1]
            DONES = jnp.stack(dones, axis=1)
        else:
            E = raw

        # 4. lockstep acceptance: longest accepted prefix (greedy:
        # draft == target argmax; sampled: the rejection test above).
        # Rows that are (or go) done never throttle the batch — their
        # post-eos emissions are pad regardless of any logits, so the
        # acceptance comparison is moot for those columns.
        match = accept if sampled else (D == G[:, :gamma])
        if eos_id >= 0:
            # DONES[:, j] is a sticky superset of the entry `done`, so
            # it alone forces acceptance for every post-eos column
            match = match | DONES[:, :gamma]
        m_row = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                        axis=1)
        m = jnp.min(m_row)                              # scalar, 0..gamma
        done_new = (jnp.take_along_axis(
            DONES, jnp.full((b, 1), m), axis=1)[:, 0]
            if eos_id >= 0 else done)

        # The slice write covers gamma+1 columns; columns beyond m+1
        # hold unaccepted values that the NEXT round's write (starting
        # exactly at emitted+m+1) overwrites before anything reads them.
        buf = jax.lax.dynamic_update_slice(
            buf, E.astype(buf.dtype), (0, t_prompt + emitted))
        cur_new = E[jnp.arange(b), m]        # e_m per row (pad if done)
        # token at the new pos-1: e_{m-1} when m >= 1, else cur
        g_prev = jnp.take_along_axis(
            E, jnp.full((b, 1), jnp.maximum(m - 1, 0)), axis=1)[:, 0]
        prev_new = jnp.where(m > 0, g_prev, cur)
        # the draft's caches CARRY (dkc/dvc): accepted-prefix entries
        # match the emitted tokens, stale rejected entries sit at
        # positions >= pos+m+1 and are rewritten before any later
        # query can attend them (write-before-attend + causal mask)
        return (buf, emitted + m + 1, cur_new, prev_new, pos + m + 1,
                done_new, tk, tv, dkc, dvc)

    done0 = (first == eos_id) if eos_id >= 0 else jnp.zeros((b,), bool)
    state = (buf0, jnp.int32(1), first, tokens[:, -1].astype(first.dtype),
             jnp.int32(t_prompt), done0, tk, tv, dk, dv)
    rounds0 = jnp.int32(0)

    def cond_r(sr):
        return cond(sr[0])

    def body_r(sr):
        return body(sr[0], sr[1]), sr[1] + 1

    final, rounds = jax.lax.while_loop(cond_r, body_r, (state, rounds0))
    buf, emitted = final[0], final[1]
    out = {"Out": [buf[:, :t_prompt + max_new]]}
    # acceptance observability. Rounds counts VERIFICATION rounds (the
    # prefill forward that emits the first token is not one), so the
    # achieved speculation efficiency is (Emitted - 1) / Rounds,
    # bounded by the (gamma + 1) ceiling.
    out["Rounds"] = [rounds]
    out["Emitted"] = [jnp.minimum(emitted, max_new)]
    return out


# ---------------------------------------------------------------------
# Paged KV cache — the continuous-batching serving layout.
#
# The fused llama_generate program owns a [L, B, total, g, hd] cache
# whose batch axis is the REQUEST batch: every request in the program
# starts and ends together. Continuous batching needs requests to join
# and leave every step, which under XLA's fixed-shape rule means the
# dynamism must live inside a static buffer: a page pool
# [n_pages, page_size, g, hd] per layer, plus a per-slot page TABLE
# (fed each step, so allocation is a host-side integer problem, never
# a recompile). Page 0 is the null page — inactive slots point every
# table entry at it, their writes land there, and nothing ever reads
# it back because the attention mask bounds each row at its own
# length. Reads gather pages through the table; writes scatter at
# (table[pos // page_size], pos % page_size) — write-before-attend,
# exactly like the contiguous cache.
#
# Numerics contract (pinned by tests/test_decode_serving.py): every
# row's computation depends only on its own row and its own pages, so
# a request's greedy tokens are bit-identical whether it runs alone or
# co-scheduled with any mix of neighbours — the decode-step executable
# shape never changes, and cross-row coupling does not exist.
# ---------------------------------------------------------------------

# every name the engine's counters take from a paged program's ``Stats``
# output, in its order (serving/decode_engine.py ticks them; docs/
# SERVING.md, "Metrics reference"). Assignments are counted over the
# router's whole width, everything else over the experts held, which are
# fewer where the model is one chip's share of an expert-parallel layer.
# The decode ones are counted by decode dispatches alone: a prefill
# touches every expert and would hide what a step must read.
PAGED_STATS = ("moe_assignments_total", "moe_max_load_total",
               "moe_decode_expert_calls_total",
               "moe_decode_experts_touched_total",
               "latent_tokens_read_total", "moe_held_assignments_total")
# what a model that mixes kinds of attention layer counts besides, over
# decode steps alone: the cache positions its active rows attended, summed
# over the layers that keep the whole sequence and over the layers with a
# window (layers x rows x positions, so entry bytes x these is what the
# steps had to read of the cache)
HYBRID_STATS = PAGED_STATS + ("attn_full_positions_total",
                              "attn_window_positions_total")
# and a model some of whose layers are state-space mixers: the states its
# active rows updated over decode steps (layers x rows), and the real
# positions its prefill windows scanned (layers x positions)
SSM_STATS = HYBRID_STATS + _state_stats("ssm")
# or gated delta-rule layers: the same two, under the mixer's name
DELTA_STATS = HYBRID_STATS + _state_stats("delta")
# or gated short convolutions: the tails updated and the positions tapped
CONV_STATS = HYBRID_STATS + _state_stats("conv")
# or Kimi-delta-attention layers beside a LATENT layer: the rule's two under
# this mixer's name, and the cache positions the active rows attended in
# the latent layers over decode steps (layers x rows x positions: the
# stored entry's bytes x this is what the steps read of the latent pool)
KDA_LATENT_STATS = HYBRID_STATS + _state_stats("kda") + (
    "attn_latent_positions_total",)
# and a model whose stack is run several times a token, over decode steps
# alone: the layer passes its active rows went through (passes x layers a
# row a step, counted by the loop that ran them) and the cache positions
# they attended, summed over those layer passes (a layer's K and V entry
# bytes x these is what the steps had to read of the cache)
LOOP_STATS = PAGED_STATS + ("loop_layer_passes_total",
                            "loop_positions_attended_total")

# keys a prefill window folds at a time where its fold is plain jax.numpy
# (every backend but the chip, a width the kernel does not admit): scores
# of [heads, window, keys] float32 in HBM, never of the whole cache. At
# most _KEY_BLOCK keys, and fewer where heads x window is so large that
# one score pass would pass _SCORE_BYTES (128 heads over a 1,024-token
# window: 1,024 keys; 32 heads over 2,048: all 2,048). Where the fold is
# the kernel ``prefill_fold`` the scores never reach HBM and a block is
# pallas_attention.PREFILL_VISIT_KEYS positions, whatever the heads
_KEY_BLOCK = 2048
_SCORE_BYTES = 2 ** 29

# widths of the chip's minor-axis tile: a pool whose entry is not a whole
# number of them is handed to every program with another axis innermost
# and re-laid, whole, on its way to the gather and to the output (PERF.md
# section 6, PR 33 and PR 34)
_LANE_TILE = 128


def whole_tiles(width):
    """The width a cache stores a ``width``-wide entry at: the next whole
    number of lane tiles (a width that is one already: itself)."""
    return -(-int(width) // _LANE_TILE) * _LANE_TILE


def _padded(x, width):
    """``x`` [..., w] at ``width``: zeros behind it where that is wider."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _as_stored(entry, pool):
    """``entry`` [..., width] as ``pool`` [..., stored width] keeps it:
    zeros behind it where the pool is wider."""
    return _padded(entry, pool.shape[-1])


def _over(rows, ndim):
    """``rows`` [n] against an ``ndim``-dimensional array of n rows."""
    return rows[(slice(None),) + (None,) * (ndim - 1)]


def _fold_carry(b, n_heads, t, vd):
    """``prefill_fold``'s carry before the first block: (acc [B, heads,
    T, vd], ml [B, heads, T, 128]: lane 0 the running maximum, lane 1 the
    denominator), float32. The lanes are told apart by an iota: a
    constant scattered into zeros is folded into a literal of the carry's
    size (33-67 MB a program held on the chip and read back from the
    compile cache at every set-up: PERF.md section 6, PR 44)."""
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (b, n_heads, t, _LANE_TILE), 3)
    return (jnp.zeros((b, n_heads, t, vd), jnp.float32),
            jnp.where(lane == 0, jnp.float32(-1e30), jnp.float32(0)))


def _folded(carry):
    """(m, l, acc) of ``prefill_fold``'s carry after the last block."""
    acc, ml = carry
    return ml[..., 0], ml[..., 1], acc


class _PagedRunner:
    """Paged twin of _make_cached_runner, closed over one model's
    stacked weights and its ``BlockKinds``. A model's cache is a tuple of
    pools ``[L, n_pages, page_size, *entry]``, one per entry a token leaves
    in a layer (GQA: K and V ``[g, hd]``; latent attention: one ``[kv_rank
    + rope_dim]``), AN ENTRY STORED AT WHOLE LANE TILES (latent attention's
    576 values 640 wide, zeros behind them, or the chip re-lays the whole
    pool on its way into every program: ``cache_spec()``; PERF.md section
    6, PR 33 and 34). Page 0 is the null page: the writes of inactive slots
    and of unallocated tails land there, in no defined order (the engine
    lets no live row read them). TWO ENTRIES, both against the pools:

    - ``forward(h, *pools, table, pos0, t_len)`` — a WINDOW of tokens
      (the prefill ops, the speculative round's windows): each layer
      writes the window's entries at ``[layer, table[row, p // page_size],
      p % page_size]`` and attends the row's pages of that layer. Plain
      GQA attends the layer's gathered rows (``_attend_math``); a layer
      that keeps the whole sequence beside other kinds, and latent
      attention, which EXPANDS the latents it sees into per-head keys and
      values, fold the row's pages a block of keys at a time under a
      running softmax (``_gqa_blocked``, ``_latent_expanded``: jax.numpy,
      or a block a call of the kernel ``prefill_fold`` where
      ``prefill_in_kernel`` says so: PERF.md section 6, PR 44).
    - ``decode_step(h, *cache, table, pos)`` — ONE token a row (the step
      scan of ``_paged_decode``; the draft's steps): a layer writes its
      entry into its page and calls its paged attention function
      (``_paged_step``;
      pallas_attention.py ``paged_gqa_decode``, ``paged_flat_decode``,
      ``paged_latent_decode``: the Pallas kernel where its gate passes, the
      jax.numpy reference where not: the CALL decides, no program's shape
      does). Latent attention ABSORBS the expansion into its query and its
      output and reads the latents as they lie.

    The layer scan CARRIES the whole [L, ...] pools beside ``h`` and scans
    over (weights, layer index): as a scan's ``xs`` / ``ys`` they would be
    rebuilt whole on every call; carried, they alias through every loop of
    a program (tests/test_paged_cache_inplace.py). ``lead``: the leading
    layers whose feed-forward is dense, before the scan, on the same pools'
    first layers. int8 ``<Slot>Scale`` ride in ``params``.

    A CACHE KIND is how long a layer's entries live; a model whose
    ``BlockKinds`` mix kinds (``attn_kinds`` / ``layer_kinds``) has for
    each its own pools (an entry FLAT in its page, ``[.., heads *
    width]``), parameter stack (``stacks[prefix]``) and table, and
    ``_stack_forward`` walks the pattern a run of same-kind layers at a
    time. ``sequence``: as long as the request, through ``table`` [B,
    pages_per_seq]. ``window`` (a layer that attends the ``w - 1``
    positions before the query): a RING of ``ring_table.shape[1]`` pages a
    row, position ``p`` at page ``(p // page_size) % ring pages``. THE ONE
    VIEW LEFT is this kind's: it has no kernel (a window and a learned
    sink), so a decode dispatch gathers the rows' rings once
    (``open_rings``), its steps write and attend that, and ``close_rings``
    copies their entries back (0.08 GB in MiMo's share; ROADMAP.md Design
    2). ``state`` (the mixers of _STATE_MIXERS): ONE entry a row for
    the row's life through ``state_table`` [B, 1], NOT protected by the
    length mask: a window that starts a row starts from zeros (``fresh``),
    a decode step updates the live rows' entries where they lie. A STACK
    RUN SEVERAL TIMES A TOKEN (``BlockKinds.passes``): the pools are
    ``passes`` times as deep as the weights, layer ``j`` of pass ``s`` at
    ``s * layers + j``; the final norm and the exit gate close every pass."""

    def __init__(self, params, emb_w, fnorm, head, *, n_heads, n_kv,
                 base, eps, page_size, head_scale=None, moe_top_k=2,
                 kinds=None, lead=None, stacks=None):
        self.params = params
        self.emb_w = emb_w
        self.fnorm = fnorm
        self.head = head
        self.head_scale = head_scale
        self.n_heads = n_heads
        self.n_kv = n_kv
        self.base = base
        self.eps = eps
        self.page_size = page_size
        self.moe_top_k = moe_top_k
        self.kinds = kinds or BlockKinds(
            n_heads=n_heads, n_kv=n_kv, base=base, eps=eps,
            moe_top_k=moe_top_k)
        self.lead = lead
        self.stacks = stacks    # attention kind's prefix -> its layers
        self.ring_table = None  # [B, ring pages]: the window kinds' table
        self.state_table = None  # [B, 1]: the state kinds' entry a row
        self.fresh = False      # a prefill window from position 0: the
                                # rows' states start from zeros, unread
        self.lens = None        # [B]: a prefill window's real tokens
        self.valid = None       # [B, T] bool: the tokens Stats counts
        self.pick_at = None     # [B]: the window position Picks reports
        self.leaves_table = False  # a window whose positions may run to
                                # ``kmax`` and beyond, where they are
                                # dropped (the speculative round's; a
                                # prefill window never leaves its table)
        self.seen = None        # positions a prefill window can see at
                                # most, where known: latent attention
                                # reads no page beyond them
        self._loads = []        # the last forward's routed loads [n, E]
        self.picks = None       # and its picks at pick_at, [n, B, K]
        self.exit_gate = None   # (w [D], b [1]) of a looped model's gate
        self.gates = None       # and its values, [passes, B, T] float32
        self.layer_passes = None  # the layer passes its last forward ran,
                                # counted where they ran
        if self.kinds.attention == "gqa":
            self.hd = self.kinds.key_dim \
                or params["Wq"].shape[-1] // n_heads
            self.rep = n_heads // n_kv

    def embed(self, tokens):
        """Token rows of the embedding; under ``mhc`` every residual
        stream starts as a copy of them."""
        h = self.emb_w[tokens]
        if self.kinds.residual == "mhc":
            h = jnp.repeat(h[..., None, :], self.kinds.n_streams, axis=-2)
        return h

    def _attend_math(self, q, k_all, v_all, q_pos, t_len):
        """GQA attention of a [B, t_len] query window against dense
        [B, kmax] caches, each row masked at its own positions. Stale
        or garbage cache contents beyond a row's length are multiplied
        by an exact softmax zero (exp(-1e30 - max) underflows to 0.0),
        so they can never perturb a live row."""
        b, kmax = k_all.shape[0], k_all.shape[1]
        qg = q.reshape(b, t_len, self.n_kv, self.rep, self.hd)
        mask = (jnp.arange(kmax, dtype=jnp.int32)[None, None]
                <= q_pos[:, :, None])                    # [B, T, K]
        logits = jnp.einsum("bqgrd,bkgd->bgrqk",
                            qg.astype(jnp.float32),
                            k_all.astype(jnp.float32)) / np.sqrt(self.hd)
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                         v_all.astype(jnp.float32))
        return out.astype(q.dtype).reshape(
            b, t_len, self.n_heads * self.hd)

    def _gqa_blocked(self, q, read_block, n_blocks, kb, q_pos, sink=None,
                     in_kernel=False):
        """GQA attention of a prefill window over a whole-sequence cache,
        a block of ``kb`` positions at a time (``read_block(i) -> (keys
        [B, kb, g, kd], values [B, kb, g, vd])``) under a running softmax:
        _latent_expanded's fold without the expansion. Only the blocks
        that hold a position some query may see are visited.
        ``in_kernel``: a visit is one call of ``prefill_fold`` (queries
        and keys zero-padded to whole lane tiles a head, the product's
        extra columns zeros) and this fold its reference."""
        b, t = q_pos.shape
        f32 = jnp.float32
        scale = q.shape[-1] ** -0.5
        k0, v0 = jax.eval_shape(read_block, 0)
        n_heads = q.shape[2]                # the layer's kind's own
        g, r, vd = k0.shape[2], n_heads // k0.shape[2], v0.shape[-1]

        def fold(i, carry):
            m, l, acc = carry
            kblk, vblk = read_block(i)
            qg = q.reshape(b, t, g, r, q.shape[-1])
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kblk,
                           preferred_element_type=f32) * scale
            k_pos = i * kb + jnp.arange(kb, dtype=jnp.int32)
            s = jnp.where((k_pos[None, None] <= q_pos[:, :, None])
                          [:, None, None], s, -1e30)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            w = jnp.exp(s - m2[..., None])
            a = jnp.exp(m - m2)
            acc = acc * a[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", w.astype(vblk.dtype), vblk,
                preferred_element_type=f32)
            return m2, l * a + jnp.sum(w, axis=-1), acc

        if in_kernel:
            wide = whole_tiles(q.shape[-1])
            qf = _padded(q, wide).reshape(b, t, -1)

            def visit(i, carry):
                kblk, vblk = read_block(i)
                return prefill_fold(
                    qf, _padded(kblk, wide).reshape(b, kb, -1),
                    vblk.reshape(b, kb, -1), q_pos[:, 0], i * kb, *carry,
                    scale=scale)

            step, init = visit, _fold_carry(b, n_heads, t, vd)
        else:
            step, init = fold, (jnp.full((b, g, r, t), -1e30, f32),
                                jnp.zeros((b, g, r, t), f32),
                                jnp.zeros((b, g, r, t, vd), f32))
        seen = jnp.minimum(jnp.max(q_pos) // kb + 1, n_blocks)
        carry = jax.lax.fori_loop(0, seen, step, init)
        m, l, acc = carry if not in_kernel else (
            x.reshape((b, g, r) + x.shape[2:]) for x in _folded(carry))
        if sink is not None:
            sk = sink.astype(f32).reshape(1, g, r, 1)
            m2 = jnp.maximum(m, sk)
            a = jnp.exp(m - m2)
            acc, l = acc * a[..., None], l * a + jnp.exp(sk - m2)
        out = jnp.moveaxis(acc / l[..., None], 3, 1)   # [B, T, g, r, vd]
        return out.astype(q.dtype).reshape(b, t, -1)

    def _ring_pages(self, pos):
        """(page, offset) of positions ``pos`` [B, n] in the rows' rings."""
        ps, n_ring = self.page_size, self.ring_table.shape[1]
        return (jnp.take_along_axis(self.ring_table, (pos // ps) % n_ring,
                                    axis=1), pos % ps)

    def _window_prefill(self, q, entries, rings, lyr, q_pos, window,
                        sink):
        """A prefill window through a window layer: (out, the rings
        written). The window's own keys and values ``entries`` and the
        ``window`` positions before its first, which the ring holds (read
        before anything is written), are cut into blocks of ``window``
        positions, and a block of queries attends its own block and the
        one before it: no other key can lie inside its band. Then the
        last ring's worth of the row's REAL positions (``lens``) goes
        into the ring; a padding position is never written, it would
        take the place of a real one."""
        b, t = q_pos.shape
        w = window
        pos0 = q_pos[:, 0]
        prev = pos0[:, None] - w + jnp.arange(w, dtype=jnp.int32)[None]
        pg, off = self._ring_pages(jnp.maximum(prev, 0))
        nb = -(-t // w)

        def banded(x, before=None):
            """[B, t, ...] (behind ``before`` [B, w, ...]) -> the blocks
            [B * nb, w or 2w, ...]."""
            x = jnp.pad(x, [(0, 0), (0, nb * w - x.shape[1])]
                        + [(0, 0)] * (x.ndim - 2))
            if before is None:
                return x.reshape((b * nb, w) + x.shape[2:])
            x = jnp.concatenate([before, x], axis=1).reshape(
                (b, nb + 1, w) + x.shape[2:])
            return jnp.concatenate([x[:, :-1], x[:, 1:]], axis=2).reshape(
                (b * nb, 2 * w) + x.shape[3:])

        at = pos0[:, None] + jnp.arange(nb * w, dtype=jnp.int32)[None]
        keys, values = (
            banded(e, ring[lyr, pg, off].reshape((b, w) + e.shape[2:]))
            for e, ring in zip(entries, rings))
        out = masked_attention(
            banded(q), keys, values, at.reshape(b * nb, w),
            k_pos=banded(at, prev), window=w, sink=sink)
        out = out.reshape(b, nb * w, -1)[:, :t]
        # the ring: the last ``ring`` real positions of the window
        n_ring = self.ring_table.shape[1] * self.page_size
        j = self.lens[:, None] - n_ring \
            + jnp.arange(n_ring, dtype=jnp.int32)[None]
        pg, off = self._ring_pages(pos0[:, None] + j)
        src = jnp.clip(j, 0, t - 1)
        rows = jnp.arange(b)[:, None]
        rings = tuple(
            ring.at[lyr, jnp.where(j >= 0, pg, ring.shape[1]), off].set(
                e[rows, src].reshape(b, n_ring, -1), mode="drop")
            for e, ring in zip(entries, rings))
        return out, rings

    def _state_prefill(self, p, z, mine, lyr, pos0, spec):
        """A prefill window through a layer that keeps a state (the
        kind's ``mixer``: ops/ssm.py, ops/delta_rule.py): (y, the kind's
        pools written). The rows' state and tail come from their entries
        where the window continues a row (``pos0 > 0``) and are zeros
        where it starts one; ``fresh`` (every row starts): the entries are
        not read at all."""
        b = z.shape[0]
        s_pool, t_pool = _state_pools(mine)
        entry = self.state_table[:, 0]
        with jax.named_scope("cache/" + spec["name"]):
            state0 = None
            if self.fresh:
                if s_pool is not None:
                    state0 = jnp.zeros((b,) + s_pool.shape[2:],
                                       s_pool.dtype)
                tail0 = jnp.zeros((b, t_pool.shape[2]), t_pool.dtype)
            else:
                goes_on = pos0 > 0
                if s_pool is not None:
                    state0 = jnp.where(_over(goes_on, s_pool.ndim - 1),
                                       s_pool[lyr, entry], 0)
                tail0 = jnp.where(goes_on[:, None], t_pool[lyr, entry], 0)
        y, state, tail = _STATE_MIXERS[spec["mixer"]].window(
            p, z, state0, tail0.reshape(b, -1, p["ConvW"].shape[-1]),
            self.lens, self.kinds.eps, **spec.get("rule", {}))
        with jax.named_scope("cache/" + spec["name"]):
            if s_pool is not None:
                s_pool = s_pool.at[lyr, entry].set(
                    state.astype(s_pool.dtype))
            t_pool = t_pool.at[lyr, entry].set(
                tail.reshape(b, -1).astype(t_pool.dtype))
        return y, [t_pool] if s_pool is None else [s_pool, t_pool]

    def _state_step(self, p, z, mine, lyr, spec):
        """A decode step through a layer that keeps a state, run IN THE
        ENTRIES' ORDER against the kind's pools themselves: (y [B, 1, C], the
        pools written). The step's inputs, a row each and small, are
        scattered to their rows' entries, the kind's mixer steps every
        entry of the layer where it lies (its ``step`` takes the state pool
        and hands it back written, each mixer owns how its slab is
        updated, and the entries' tails flat as their pool stores them),
        and the live rows' outputs are gathered back: the
        states, which are most of what a step moves, are neither gathered
        to the rows nor scattered back (a view of them cost 6.5 GB of
        copies a dispatch at 128 rows: PERF.md section 6, PR 39). An entry
        that no live row holds (the null entry, a free one, a chunk job's
        between its chunks) keeps what it held, and a row that is not
        live gets zeros."""
        s_pool, t_pool = _state_pools(mine)
        n = t_pool.shape[1]
        live = self.valid[:, 0]
        entry = self.state_table[:, 0]
        with jax.named_scope("cache/" + spec["name"]):
            at = jnp.where(live, entry, n)          # not live: dropped
            z_e = jnp.zeros((n, z.shape[-1]), z.dtype).at[at].set(
                z[:, 0], mode="drop")
            held = jnp.zeros((n,), bool).at[at].set(True, mode="drop")
            tail0 = t_pool[lyr]
        y_e, s_pool, tail = _STATE_MIXERS[spec["mixer"]].step(
            p, z_e, s_pool, lyr, held, tail0, self.kinds.eps,
            **spec.get("rule", {}))
        with jax.named_scope("cache/" + spec["name"]):
            t_pool = t_pool.at[lyr].set(jnp.where(
                held[:, None], tail.astype(t_pool.dtype), tail0))
            # a row that is not live reads no entry either: whatever the
            # null entry holds cannot reach the null PAGE through it
            y = jnp.where(live[:, None], y_e[entry], 0)
        return y[:, None], [t_pool] if s_pool is None else [s_pool, t_pool]

    def _kv_up(self, p):
        """A layer's latent -> per-head [key | value] expansion,
        [kv_rank, heads, nope_dim + v_dim]."""
        k = self.kinds
        return p["Wkvb"].reshape(k.kv_rank, k.n_heads,
                                 k.nope_dim + k.v_dim)

    def _latent_expanded(self, p, q, read_block, n_blocks, kb, q_pos,
                         in_kernel=False):
        """Latent attention of a prefill window, expanded: each block of
        ``kb`` cache positions (``read_block(i) -> [B, kb, entry]``) is
        expanded to per-head keys and values and folded into a running
        softmax, so no [heads, window, kmax] array is ever live. Only
        the blocks that hold a position some query may see are visited.
        Block 0 holds position 0, which every query sees, so the running
        maximum is real before any wholly masked block meets it.
        ``in_kernel``: a visit expands its block as here and folds it in
        one call of ``prefill_fold`` (a head's own key part in one
        product, the rotated part all heads share, zero-padded to whole
        lane tiles, in a second); this fold is its reference."""
        k = self.kinds
        q_nope, q_pe = q
        b, t = q_pos.shape
        w_up = self._kv_up(p)
        f32 = jnp.float32

        def fold(i, carry):
            m, l, acc = carry
            blk = read_block(i)
            kv = jnp.einsum("bkr,rhd->bkhd", blk[..., :k.kv_rank], w_up)
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope,
                            kv[..., :k.nope_dim],
                            preferred_element_type=f32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_pe,
                              blk[..., k.kv_rank:k.kv_rank + k.rope_dim],
                              preferred_element_type=f32)) \
                * k.softmax_scale
            k_pos = i * kb + jnp.arange(kb, dtype=jnp.int32)
            s = jnp.where((k_pos[None, None] <= q_pos[:, :, None])[:, None],
                          s, -1e30)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            w = jnp.exp(s - m2[..., None])
            a = jnp.exp(m - m2)
            acc = acc * a[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", w.astype(kv.dtype),
                kv[..., k.nope_dim:], preferred_element_type=f32)
            return m2, l * a + jnp.sum(w, axis=-1), acc

        with jax.named_scope("mla/expand"):
            if in_kernel:
                wide = whole_tiles(k.rope_dim)
                qn = q_nope.reshape(b, t, -1)
                qp = _padded(q_pe, wide).reshape(b, t, -1)
                w_k, w_v = w_up[..., :k.nope_dim], w_up[..., k.nope_dim:]

                def visit(i, carry):
                    blk = read_block(i)
                    lat = blk[..., :k.kv_rank]
                    return prefill_fold(
                        qn,
                        jnp.einsum("bkr,rhd->bkhd", lat, w_k).reshape(
                            b, kb, -1),
                        jnp.einsum("bkr,rhd->bkhd", lat, w_v).reshape(
                            b, kb, -1),
                        q_pos[:, 0], i * kb, *carry, scale=k.softmax_scale,
                        shared=(qp, _padded(
                            blk[..., k.kv_rank:k.kv_rank + k.rope_dim],
                            wide)))

                step, init = visit, _fold_carry(b, k.n_heads, t, k.v_dim)
            else:
                step, init = fold, (
                    jnp.full((b, k.n_heads, t), -1e30, f32),
                    jnp.zeros((b, k.n_heads, t), f32),
                    jnp.zeros((b, k.n_heads, t, k.v_dim), f32))
            seen = jnp.minimum(jnp.max(q_pos) // kb + 1, n_blocks)
            carry = jax.lax.fori_loop(0, seen, step, init)
            _, l, acc = _folded(carry) if in_kernel else carry
            out = jnp.moveaxis(acc / l[..., None], 1, 2)
        return out.astype(q_nope.dtype).reshape(b, t,
                                                k.n_heads * k.v_dim)

    def _stack_forward(self, h, pools, q_pos, attend_write):
        """The layers, shared by both entries: the leading layers one by
        one, then the scan over the stacked ones (where the model mixes
        attention kinds: a scan over each run of same-kind layers, in
        the pattern's order). The whole [L, ...] caches ride in the carry
        and the layer index in ``xs``; ``attend_write(p, q, entries,
        pools, layer, kind) -> (out, pools2)`` owns the cache update +
        attend of layer ``layer`` of its kind's pools (``kind`` None:
        the model has one kind)."""
        self._loads, self.picks = [], None

        def layer(h, pools, p, lyr, ffn=None, kind=None):
            held = {}

            def attend(q, entries):
                out, held["pools"] = attend_write(p, q, entries, pools,
                                                  lyr, kind)
                return out

            kinds = self.kinds if kind is None else self.kinds.of(kind)
            h, routing = block_forward(kinds, p, h, q_pos, attend,
                                       ffn=ffn, valid=self.valid)
            if routing is not None:         # (load, picks at pick_at)
                at = (jnp.zeros((h.shape[0],), jnp.int32)
                      if self.pick_at is None else self.pick_at)
                routing = (routing[0],
                           routing[1][jnp.arange(h.shape[0]), at])
            return h, held["pools"], routing

        lk = self.kinds.layer_kinds
        n_lead = 0
        # layers of each kind already run: the next one's place in its
        # kind's pools
        done = [0] * len(self.kinds.attn_kinds or ())
        if self.lead is not None:
            n_lead = jax.tree_util.tree_leaves(self.lead)[0].shape[0]
            for i in range(n_lead):
                kind = None if lk is None else lk[i]
                h, pools, _ = layer(
                    h, pools, {s: w[i] for s, w in self.lead.items()},
                    i if kind is None else done[kind], ffn="swiglu",
                    kind=kind)
                if kind is not None:
                    done[kind] += 1

        def split(params):
            """(held, sliced): the routed experts' stacks stay whole
            outside the scan's xs: a scan slices its xs, and a slice that
            feeds the grouped matmul is a copy of every expert of the
            layer, on every call."""
            held = {s: w for s, w in params.items()
                    if s in _EXPERT_SLOTS and self.kinds.ffn == "routed"
                    and s + "Scale" not in params}
            return held, {s: w for s, w in params.items()
                          if s not in held}

        if lk is None:
            held, sliced = split(self.params)
            # the layers are the weights', not the pools': a stack that is
            # run ``passes`` times has ``passes`` cache layers a layer
            n = next(iter(self.params.values())).shape[0]

            def stack(h, pools, first):
                """The stacked layers once, layer j on cache layer
                ``first + j``."""
                def scanned(carry, xs):
                    p = dict(xs[0], **held)
                    if held:
                        p["ExpertsOf"] = xs[1]
                    h, pools, routing = layer(*carry, p, xs[1] + first)
                    return (h, pools), routing

                return jax.lax.scan(
                    scanned, (h, pools),
                    (sliced, jnp.arange(n, dtype=jnp.int32)))

            if self.kinds.passes == 1:
                (h, pools), routing = stack(h, pools, n_lead)
                if routing is not None:
                    self._loads, self.picks = routing
                return h, pools
            if n_lead:
                raise ValueError("leading layers before a stack that is "
                                 f"run {self.kinds.passes} times")

            def one_pass(carry, s):
                """Pass ``s`` of the same weights over its own ``n`` cache
                layers, then what closes a pass (_close_pass)."""
                with jax.named_scope("loop/pass"):
                    (h, pools), _ = stack(*carry, s * n)
                h, gate = self._close_pass(h)
                return (h, pools), (gate, jnp.int32(n))

            (h, pools), (self.gates, layers) = jax.lax.scan(
                one_pass, (h, pools),
                jnp.arange(self.kinds.passes, dtype=jnp.int32))
            self.layer_passes = jnp.sum(layers)
            return h, pools

        # runs of same-kind layers: (kind, first layer of the run in its
        # kind's stack, layers). A run's scan takes layer j of the stack
        # inside its body, which is what a scan does with its xs; slicing
        # the run out of the stack beforehand would copy its weights
        first, nxt, routings = list(done), [0] * len(done), []
        for kind, run in itertools.groupby(lk[n_lead:]):
            count = len(list(run))
            start, nxt[kind] = nxt[kind], nxt[kind] + count
            spec = self.kinds.attn_kinds[kind]
            held, sliced = split(self.stacks[spec["stack"]])
            if spec["window"] is None and not _keeps_state(spec):
                # a layer that keeps the whole sequence is taken by its
                # own number, not a scan's (the chip's programs hold a
                # kernel instance a layer so: PERF.md section 6, PR 33 and
                # 42). Such layers are one in six of the pattern, so this
                # is no loop over the stack
                for j in range(start, start + count):
                    p = dict({s: w[j] for s, w in sliced.items()}, **held)
                    if held:
                        p["ExpertsOf"] = jnp.int32(j)
                    h, pools, routing = layer(h, pools, p, j + first[kind],
                                              kind=kind)
                    routings.append(None if routing is None else tuple(
                        r[None] for r in routing))
                continue

            def scanned(carry, j, kind=kind, held=held, sliced=sliced):
                p = dict({s: w[j] for s, w in sliced.items()}, **held)
                if held:
                    p["ExpertsOf"] = j
                h, pools, routing = layer(*carry, p, j + first[kind],
                                          kind=kind)
                return (h, pools), routing

            (h, pools), routing = jax.lax.scan(
                scanned, (h, pools),
                jnp.arange(start, start + count, dtype=jnp.int32))
            routings.append(routing)
        if all(r is not None for r in routings):
            self._loads, self.picks = (
                jnp.concatenate([r[i] for r in routings]) for i in (0, 1))
        return h, pools

    # -- paged form (prefill) --------------------------------------------
    def forward(self, h, *pools_table_pos0_len):
        *pools, table, pos0, t_len = pools_table_pos0_len
        b = h.shape[0]
        ps = self.page_size
        kmax = table.shape[1] * ps
        q_pos = pos0[:, None] + jnp.arange(t_len, dtype=jnp.int32)[None]
        k = self.kinds
        shapes = [pl.shape for pl in pools]

        def key_blocks(kind=None):
            """How a layer that keeps the whole sequence reads its row's
            pages, a block of keys at a time: (its fold is the kernel,
            pages a block, blocks, the table padded to whole blocks)."""
            in_kernel = prefill_in_kernel(
                k.attention, k.attn_kinds, (k.nope_dim, k.v_dim), shapes,
                t_len, table.shape[1], self.seen, kind)
            n_read = _pages_seen(table.shape[1], self.seen, ps)
            heads = (k if kind is None else k.of(kind)).n_heads
            ppb = _pages_a_block(in_kernel, ps, n_read, heads * b * t_len)
            n_blocks = -(-n_read // ppb)
            return in_kernel, ppb, n_blocks, jnp.pad(
                table[:, :n_read], ((0, 0), (0, n_blocks * ppb - n_read)))

        # once a program, before its layers (a scanned layer reads it)
        blocks_of = {kind: key_blocks(kind) for kind in (
            [None] if k.attn_kinds is None else
            [i for i, spec in enumerate(k.attn_kinds)
             if spec["window"] is None and not _keeps_state(spec)])}

        def latent_fold(p, q, pool, lyr, kind=None):
            """A latent layer's window against its rows' pages of ``pool``,
            written already: expanded, a block of keys at a time."""
            in_kernel, ppb, n_blocks, blocks = blocks_of[kind]

            def read_block(i):
                tb = jax.lax.dynamic_slice_in_dim(blocks, i * ppb, ppb,
                                                  axis=1)
                return pool[lyr, tb].reshape(b, ppb * ps, -1)

            return self._latent_expanded(p, q, read_block, n_blocks,
                                         ppb * ps, q_pos, in_kernel)

        def attend_kind(p, q, entries, pools, lyr, kind):
            """A layer of one of several attention kinds: its own pools."""
            spec = self.kinds.attn_kinds[kind]
            mine = [pools[i] for i in spec["pools"]]
            sink = p["Sink"] if spec["sink"] else None
            if _keeps_state(spec):
                out, mine = self._state_prefill(p, q, mine, lyr, pos0,
                                                spec)
            elif _is_latent(spec):
                with jax.named_scope("attn/" + spec["name"]):
                    with jax.named_scope("cache/" + spec["name"]):
                        pg = jnp.take_along_axis(table, q_pos // ps, axis=1)
                        mine = [pl.at[lyr, pg, q_pos % ps].set(
                            _as_stored(e, pl))
                            for pl, e in zip(mine, entries)]
                    out = latent_fold(p, q, mine[0], lyr, kind)
            elif spec["window"] is not None:
                with jax.named_scope("attn/" + spec["name"]):
                    out, mine = self._window_prefill(
                        q, entries, mine, lyr, q_pos, spec["window"], sink)
            else:
                with jax.named_scope("attn/" + spec["name"]):
                    pg = jnp.take_along_axis(table, q_pos // ps, axis=1)
                    mine = [pl.at[lyr, pg, q_pos % ps].set(
                        e.reshape(b, t_len, -1))
                        for pl, e in zip(mine, entries)]
                    in_kernel, ppb, n_blocks, blocks = blocks_of[kind]

                    def read_block(i):
                        tb = jax.lax.dynamic_slice_in_dim(
                            blocks, i * ppb, ppb, axis=1)
                        return tuple(
                            pl[lyr, tb].reshape((b, ppb * ps)
                                                + e.shape[2:])
                            for pl, e in zip(mine, entries))

                    out = self._gqa_blocked(q, read_block, n_blocks,
                                            ppb * ps, q_pos, sink,
                                            in_kernel)
            pools = list(pools)
            for i, pl in zip(spec["pools"], mine):
                pools[i] = pl
            return out, tuple(pools)

        def attend_write(p, q, entries, pools, lyr, kind=None):
            if kind is not None:
                return attend_kind(p, q, entries, pools, lyr, kind)
            if self.leaves_table:
                # beyond kmax: a page past the pool's last, which the set
                # drops
                pg = jnp.where(q_pos < kmax, jnp.take_along_axis(
                    table, jnp.minimum(q_pos, kmax - 1) // ps, axis=1),
                    pools[0].shape[1])
            else:
                pg = jnp.take_along_axis(table, q_pos // ps, axis=1)
            pools = tuple(pl.at[lyr, pg, q_pos % ps].set(_as_stored(e, pl))
                          for pl, e in zip(pools, entries))
            if self.kinds.attention == "latent":
                return latent_fold(p, q, pools[0], lyr), pools
            views = [pl[lyr, table].reshape((b, kmax) + pl.shape[3:])
                     for pl in pools]
            return self._attend_math(q, *views, q_pos, t_len), pools

        h, pools = self._stack_forward(h, tuple(pools), q_pos,
                                       attend_write)
        return (h,) + tuple(pools)

    # -- the decode step --------------------------------------------------
    def _ring_pools(self):
        """(kind's name, pool index) of every pool of a window kind."""
        return [(spec["name"], i) for spec in self.kinds.attn_kinds or ()
                if spec["window"] is not None and not _keeps_state(spec)
                for i in spec["pools"]]

    def open_rings(self, pools):
        """What the steps of a dispatch carry: the pools, and in a window
        kind's place the ONE view left, its rows' rings gathered through
        ``ring_table`` in table order, [layers, B, ring, ...] (position p
        at ``p % ring``): the kind has no kernel (a window and a learned
        sink), so its steps attend the view, and ``close_rings`` writes
        their entries back."""
        cache, table = list(pools), self.ring_table
        for name, i in self._ring_pools():
            with jax.named_scope("cache/" + name):
                lyr, b = pools[i].shape[0], table.shape[0]
                cache[i] = pools[i][:, table].reshape(
                    (lyr, b, table.shape[1] * self.page_size)
                    + pools[i].shape[3:])
        return tuple(cache)

    def close_rings(self, pools, cache, pos0, n):
        """The pools after ``n`` steps from ``pos0`` [B]: what the steps
        carried, and a window kind's pool with those ``n`` positions of
        every row copied out of the view of its rings (``open_rings``):
        all that the steps wrote, so all in which the view differs from
        the pool it was gathered from. Position p lies at ``p % ring`` of
        the view and in page ``(p // page_size) % ring pages`` of the
        row's ring, and no position is beyond it."""
        back, table, ps = list(cache), self.ring_table, self.page_size
        for name, i in self._ring_pools():
            with jax.named_scope("cache/" + name):
                pages = pools[i]
                q_pos = pos0[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
                # one index an entry, the layer's too: a slice across the
                # layers has XLA re-lay the whole view, layers innermost
                lyr = jnp.arange(pages.shape[0])[:, None, None]
                rows = jnp.arange(table.shape[0])[None, :, None]
                entries = cache[i][
                    lyr, rows, (q_pos % (table.shape[1] * ps))[None]]
                # the pool is not written before the entries are out and
                # the view is dead, or both are live (PERF.md section 6,
                # PR 32)
                pages, entries = jax.lax.optimization_barrier(
                    (pages, entries))
                pg = jnp.take_along_axis(
                    table, (q_pos // ps) % table.shape[1], axis=1)
                back[i] = pages.at[
                    lyr, pg[None], (q_pos % ps)[None]].set(entries)
        return back

    def _paged_step(self, attention, table, pos, n_pages, scope=None):
        """``attend(q, entries, pools, layer) -> (out [B, 1, heads * dv],
        the pools written)``: a decode step at positions ``pos`` [B] of
        one layer of a sequence kind against its pools themselves (K and
        V; a latent model's one). The step's entry goes to ``[layer,
        table[row, pos // page_size], pos % page_size]`` (the addressing
        ``forward`` writes with; a position at or beyond ``kmax`` is
        dropped, a null table entry lands on page 0) and ``attention``
        (pallas_attention.py: the kernel where its gate passes, the
        reference where not) attends the row's pages where they lie, to
        the row's own length, ``pos + 1`` and ``kmax`` at most. ``scope``:
        the named scope the entry's write goes under, where it has one of
        its own."""
        ps = self.page_size
        kmax = table.shape[1] * ps
        at = jnp.minimum(pos, kmax - 1)
        pg = jnp.where(pos < kmax,
                       jnp.take_along_axis(table, (at // ps)[:, None],
                                           axis=1)[:, 0],
                       n_pages)
        lens = jnp.minimum(pos + 1, kmax)

        def attend(q, entries, pools, lyr):
            with contextlib.nullcontext() if scope is None \
                    else jax.named_scope(scope):
                pools = tuple(pl.at[lyr, pg, pos % ps].set(
                    e[:, 0].reshape((-1,) + pl.shape[3:]), mode="drop")
                    for pl, e in zip(pools, entries))
            out = attention(q[:, 0], *pools, lyr, table, lens)
            return out.reshape(out.shape[0], 1, -1), pools

        return attend

    def decode_step(self, h, *cache_table_pos):
        """One decode step of every row at positions ``pos`` [B] against
        what ``open_rings`` gave, through ``table`` [B, pages_per_seq], the
        sequence kinds': (h, *the same, written). A layer of a SEQUENCE
        kind (plain GQA, a mixed model's layers that keep the whole
        sequence, the latent pool) writes its entry into its page and calls
        its paged attention function (_paged_step); a state kind steps its
        entries where they lie (_state_step); a window kind writes and
        attends the view of its rings. Latent attention is ABSORBED: the
        key half of the expansion moves onto the query (``q_nope Wk^T``,
        then one product with the entries as they lie, zeros against their
        padding), the value half onto the attended latent: the mathematics
        of _latent_expanded, the cache read once and never expanded."""
        *cache, table, pos = cache_table_pos
        k = self.kinds
        latent_decode = functools.partial(
            paged_latent_decode, scale=k.softmax_scale,
            width=whole_tiles(k.kv_rank))
        if k.attn_kinds is None:
            attend = self._paged_step(
                latent_decode if k.attention == "latent"
                else paged_gqa_decode, table, pos, cache[0].shape[1])
            q_pos = pos[:, None]
        else:
            # a window of one position, and a kind's addressing taken at
            # its layers: as the mixed models' pinned programs hold them
            rows = jnp.arange(h.shape[0])
            q_pos = pos[:, None] + jnp.arange(1, dtype=jnp.int32)[None]

        def attend_kind(p, q, entries, cache, lyr, kind):
            """A layer of one of several attention kinds: its own pools,
            or the view of its rings."""
            spec = k.attn_kinds[kind]
            sink = p["Sink"] if spec["sink"] else None
            mine = [cache[i] for i in spec["pools"]]
            if _keeps_state(spec):
                out, mine = self._state_step(p, q, mine, lyr, spec)
            elif _is_latent(spec):
                with jax.named_scope("attn/" + spec["name"]):
                    out, mine = absorbed(p, q, entries, mine, lyr,
                                         self._paged_step(
                                             latent_decode, table, pos,
                                             mine[0].shape[1],
                                             "cache/" + spec["name"]))
            elif spec["window"] is None:
                with jax.named_scope("attn/" + spec["name"]):
                    out, mine = self._paged_step(
                        paged_flat_decode if sink is None else
                        functools.partial(paged_flat_decode, sink=sink),
                        table, pos, mine[0].shape[1])(
                            q, entries, mine, lyr)
            else:
                # the row's ring, position p at ``p % ring``; index i then
                # holds the last position at or before the query that
                # falls there
                with jax.named_scope("attn/" + spec["name"]):
                    ring = mine[0].shape[2]
                    if ring < spec["window"]:
                        raise ValueError(
                            f"a ring of {ring} positions cannot hold a "
                            f"window of {spec['window']}")
                    mine = [d.at[lyr, rows[:, None], q_pos % ring].set(
                        e.reshape(e.shape[:2] + (-1,)))
                        for d, e in zip(mine, entries)]
                    last = q_pos[:, -1:]
                    k_pos = last - (last - jnp.arange(
                        ring, dtype=jnp.int32)[None]) % ring
                    out = masked_attention(
                        q, *(d[lyr].reshape(d.shape[1:3]
                                            + (spec["n_kv"], -1))
                             for d in mine), q_pos, k_pos=k_pos,
                        window=spec["window"], sink=sink)
            cache = list(cache)
            for i, d in zip(spec["pools"], mine):
                cache[i] = d
            return out, tuple(cache)

        def absorbed(p, q, entries, pools, lyr, attend):
            """A latent layer's step against its ONE pool: the key half of
            the expansion on the query, the value half on what ``attend``
            (a _paged_step) gives back."""
            q_nope, q_pe = q
            w_up = self._kv_up(p)
            with jax.named_scope("mla/absorb"):
                q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope,
                                   w_up[..., :k.nope_dim])
                o_lat, pools = attend(
                    _as_stored(jnp.concatenate([q_abs, q_pe], axis=-1),
                               pools[0]),
                    [_as_stored(e, pools[0]) for e in entries], pools, lyr)
                out = jnp.einsum(
                    "bqhr,rhd->bqhd",
                    o_lat.reshape(q_abs.shape[:3] + (-1,))[..., :k.kv_rank],
                    w_up[..., k.nope_dim:])
            return out.reshape(out.shape[:2] + (-1,)), pools

        def attend_write(p, q, entries, pools, lyr, kind=None):
            if kind is not None:
                return attend_kind(p, q, entries, pools, lyr, kind)
            if k.attention != "latent":
                return attend(q, entries, pools, lyr)
            return absorbed(p, q, entries, pools, lyr, attend)

        h, cache = self._stack_forward(h, tuple(cache), q_pos,
                                       attend_write)
        return (h,) + tuple(cache)

    def _close_pass(self, h):
        """(h, gate): what closes EVERY pass of a stack that is run
        several times: the model's one final norm, whose output the next
        pass (or the head: logits_of) takes, and the exit gate on it."""
        with jax.named_scope("loop/norm"):
            h = rms_normalize(h, self.fnorm, self.eps)
        with jax.named_scope("loop/gate"):
            return self._exit_gate(h)

    def _exit_gate(self, h):
        """(h, gate [B, T] float32): a looped model's exit gate ``w . h +
        b`` on a pass's normed output. The published forward stops a token
        at the first pass where the exit probabilities built from
        ``sigmoid(gate)`` reach its threshold; at the published threshold
        of 1 that is the last pass, so every pass runs, the head reads the
        last, and NO LOGIT DEPENDS ON THE GATE. It is the model's and is
        computed all the same (ISSUE 43): the barrier ties it to ``h``,
        or the compiler would drop a value nothing reads. NOTHING served
        reads ``self.gates`` today: an exit below threshold 1 would, and
        is not built. A model without the parameters has no gate."""
        if self.exit_gate is None:
            return h, None
        w, b = self.exit_gate
        gate = jnp.einsum("btd,d->bt", h, w,
                          preferred_element_type=jnp.float32) \
            + b.astype(jnp.float32)
        return jax.lax.optimization_barrier((h, gate))

    def logits_of(self, hl):
        if self.kinds.residual == "mhc":      # the streams leave summed
            hl = jnp.sum(hl.astype(jnp.float32), axis=-2).astype(hl.dtype)
        # a stack run several times ends EVERY pass in the final norm
        # (_stack_forward): what comes here is normed already
        hn = hl if self.kinds.passes > 1 \
            else rms_normalize(hl, self.fnorm, self.eps)
        if self.head_scale is None:
            return (hn @ self.head).astype(jnp.float32)
        return qmat(hn, {"W": self.head, "WScale": self.head_scale},
                    "W", cdt=jnp.float32)

    def stats(self, decode, positions=None):
        """The last forward's counters as PAGED_STATS orders them, int32:
        token-expert pairs over the router's whole width, the fullest
        held expert's tokens and the pairs that fell on held experts,
        each summed over its routed layers; for a decode step also its
        expert-layer calls x experts held, the experts among them that a
        token reached, and the cache positions its active rows
        attended (HYBRID_STATS: by cache kind); a model with layers that
        keep a state (SSM_STATS, DELTA_STATS) also the states a decode
        step updated and the real positions a prefill window computed."""
        hybrid = self.kinds.layer_kinds is not None
        names = stats_names(self.kinds)
        out = [jnp.int32(0)] * len(names)
        for k, spec in enumerate(self.kinds.attn_kinds or ()):
            if _keeps_state(spec):
                at = names.index(
                    _state_stats(spec["mixer"])[0 if decode else 1])
                out[at] = out[at] + self.kinds.layer_kinds.count(k) \
                    * jnp.sum(self.valid)
        if decode and hybrid:
            n = jnp.where(self.valid[:, 0], positions + 1, 0)
            for k, spec in enumerate(self.kinds.attn_kinds):
                layers = self.kinds.layer_kinds.count(k)
                if _keeps_state(spec):
                    continue
                if _is_latent(spec):
                    at = names.index("attn_latent_positions_total")
                    out[at] = out[at] + layers * jnp.sum(n)
                elif spec["window"] is None:
                    out[6] = out[6] + layers * jnp.sum(n)
                else:
                    out[7] = out[7] + layers * jnp.sum(
                        jnp.minimum(n, spec["window"]))
        if len(self._loads):
            loads = self._loads                      # [layers, E held]
            out[0] = (jnp.sum(self.valid) * self.kinds.moe_top_k
                      * loads.shape[0])
            out[1] = jnp.sum(jnp.max(loads, axis=-1))
            out[5] = jnp.sum(loads)
            if decode:
                out[2] = jnp.int32(loads.shape[0] * loads.shape[1])
                out[3] = jnp.sum(loads > 0)
        if decode and self.kinds.attention == "latent" and not hybrid:
            out[4] = jnp.sum(jnp.where(self.valid[:, 0], positions + 1, 0))
        if decode and self.kinds.passes > 1:
            n = jnp.where(self.valid[:, 0], positions + 1, 0)
            out[6] = self.layer_passes * jnp.sum(self.valid)
            out[7] = self.layer_passes * jnp.sum(n)
        return jnp.stack([jnp.asarray(x, jnp.int32) for x in out])


def stats_names(kinds):
    """The counters a program of a model of these kinds returns, in its
    order: PAGED_STATS, HYBRID_STATS where it mixes kinds of layer,
    and behind them a state mixer's two where some of those layers keep
    a state (SSM_STATS, DELTA_STATS), the latent layers' positions where
    one of its kinds is latent (KDA_LATENT_STATS), LOOP_STATS where its
    stack is run several times a token."""
    if kinds.passes > 1:
        return LOOP_STATS
    if kinds.layer_kinds is None:
        return PAGED_STATS
    return HYBRID_STATS + tuple(
        name for mixer in _STATE_MIXERS
        if any(k.get("mixer") == mixer for k in kinds.attn_kinds)
        for name in _state_stats(mixer)) + (
            ("attn_latent_positions_total",)
            if any(_is_latent(k) for k in kinds.attn_kinds) else ())


def decode_in_place(attention, attn_kinds, pool_shapes, kind=None):
    """Whether the decode program of a model with these block kinds, over
    pools of these shapes, attends through a Pallas kernel: a REPORT, for
    whoever builds the program and wants to say so (a decode bundle's
    ``in_place``, the engine's ``decode_in_place_total``). It chooses
    nothing: every decode step runs against the pools, and each paged
    attention call asks the same gate itself (pallas_attention.py). A
    model with one kind of layer, on a backend that runs the kernels:
    plain GQA with K and V pools of one shape and whole-tile heads
    (paged_gqa_usable); latent attention with ONE pool whose entries lie
    flat at whole lane tiles (paged_latent_usable). A model that mixes
    kinds of layer is asked KIND BY KIND (``kind`` None: whether any
    does): the kind that keeps the whole sequence, has attention for its
    mixer and no sink, its two pools' entries flat at whole lane tiles
    (paged_flat_usable; a value head that is a part of a tile:
    paged_packed_usable); a LATENT kind, its one pool as a latent model's;
    a window kind and a state kind have no kernel."""
    if attn_kinds is None and attention == "latent":
        return paged_latent_usable(pool_shapes)
    if attn_kinds is None:
        return (attention == "gqa" and len(pool_shapes) == 2
                and paged_gqa_usable(*pool_shapes))
    if kind is None:
        return any(decode_in_place(attention, attn_kinds, pool_shapes, k)
                   for k in range(len(attn_kinds)))
    spec = attn_kinds[kind]
    mine = [pool_shapes[i] for i in spec["pools"]]
    if _is_latent(spec):
        return paged_latent_usable(mine)
    return (spec.get("mixer", attention) == "gqa"
            and spec["window"] is None and not _keeps_state(spec)
            and not spec["sink"] and len(mine) == 2
            and (paged_flat_usable(*mine, spec["n_kv"])
                 or paged_packed_usable(*mine, spec["n_kv"])))


def state_step_in_kernel(attn_kinds, pool_specs):
    """Whether the decode program of a model with these block kinds, over
    the pools ``pool_specs`` [(shape, dtype)], steps the entries of its
    state layers through a Pallas kernel: a REPORT, as ``decode_in_place``
    is (a decode bundle's ``state_in_kernel``, the engine's
    ``state_step_in_kernel_total``), which chooses nothing: every state
    layer's ``step`` asks its mixer's own gate (``step_in_kernel`` of
    ops/ssm.py and of ops/delta_rule.py: the backend, and the state pool's
    shape and type; the gated short convolution keeps no state)."""
    return any(
        _keeps_state(spec) and _STATE_MIXERS[spec["mixer"]].step_in_kernel(
            *pool_specs[spec["pools"][0]])
        for spec in attn_kinds or ())


def _experts_gate(param_tables, gate, rows):
    """Whether any of the stacks ``param_tables`` (the programs' layer
    parameters, slot -> (suffix, shape, dtype) a stack) holds routed
    experts that ``gate`` (one of ops/moe.py's) admits at ``rows`` rows,
    asked with what the call will be given: the stacks' shapes and types,
    and ``held`` where the experts are a share of the router's width."""
    def asks(table):
        w_gate, w_down, router = (table.get(slot) for slot in (
            "MoeWGate", "MoeWDown", "MoeRouter"))
        if w_gate is None:
            return False
        w_gate, w_down = (jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt))
                          for _, shape, dt in (w_gate, w_down))
        whole = w_gate.shape[-3] == router[1][-1]
        return gate(rows, w_gate, w_down,
                    None if whole else (0, router[1][-1]))

    return any(asks(table) for table in param_tables)


def prefill_experts_in_kernel(param_tables, t_len):
    """Whether a prefill program over a window of ``t_len`` tokens puts its
    routed layers' sorted pairs through the Pallas kernel
    ``moe_grouped_rows``: a REPORT, as ``decode_in_place`` is (a prefill or
    chunk bundle's ``experts_in_kernel``, the engine's
    ``prefill_experts_in_kernel_total``), which chooses nothing: every
    routed layer's ``moe_apply_sorted`` asks the gate itself (ops/moe.py
    ``grouped_rows_usable``: the backend, the rows, the widths; a share is
    admitted as a whole layer is). ``param_tables``: the programs' layer
    parameters, slot -> (suffix, shape, dtype) a stack; whether any
    stack's does."""
    from . import moe
    return _experts_gate(param_tables, moe.grouped_rows_usable, t_len)


def decode_experts_in_kernel(param_tables, rows):
    """Whether a decode program of ``rows`` rows puts its routed layers
    through a Pallas kernel, ``moe_few_rows`` at 128 rows or fewer and
    ``moe_grouped_rows`` (behind the sort) at more: the same REPORT for a
    decode step (the decode bundle's ``experts_in_kernel``, the engine's
    ``decode_experts_in_kernel_total``), asked of both gates as
    ``moe_apply_sorted`` asks them where it lowers (the backend, the rows,
    the widths; a share is admitted as a whole layer is)."""
    from . import moe
    return any(_experts_gate(param_tables, gate, rows) for gate in (
        moe.few_rows_usable, moe.grouped_rows_usable))


def _pages_seen(n_pages, seen, page_size):
    """The pages of a row's ``n_pages`` that hold a position a prefill
    window may see (``seen`` positions at most; None: not known)."""
    return n_pages if seen is None else min(n_pages, -(-seen // page_size))


def _pages_a_block(in_kernel, page_size, n_read, score_rows):
    """The pages a prefill window folds at a time: a block of
    PREFILL_VISIT_KEYS positions where its fold is the kernel; else of
    _KEY_BLOCK, or as many as keep [score_rows, keys] float32 scores
    under _SCORE_BYTES."""
    keys = _pa.PREFILL_VISIT_KEYS if in_kernel \
        else min(_KEY_BLOCK, _SCORE_BYTES // (4 * score_rows))
    return max(1, min(keys // page_size, n_read))


def prefill_in_kernel(attention, attn_kinds, latent_widths, pool_shapes,
                      t_len, n_pages, seen, kind=None):
    """Whether a prefill op of a model with these block kinds, over pools
    of these shapes, a window of ``t_len`` tokens against a table of
    ``n_pages`` pages (``seen``: the positions it can see at most, where
    known), folds its attention over the whole sequence through the
    kernel ``prefill_fold`` and not in plain jax.numpy: read off what the
    op is given, by ``_PagedRunner.forward`` where it lowers and by
    whoever builds its program. Latent attention
    (``latent_widths``: its heads' own key and value widths): both whole
    lane tiles. A model that mixes kinds of layer is asked KIND BY KIND
    (``kind`` None: whether any is): the kind that keeps the whole
    sequence and attends, its entries flat in their pages and a value
    head whole lane tiles (a key head is padded to them). And, for both,
    a backend that runs the kernel and a window and a block of keys that
    cut into its tiles. A LATENT kind among others is asked as a latent
    model is. A model with one kind of plain GQA layer attends
    a layer's gathered rows (``_attend_math``) and is not asked."""
    if attn_kinds is None:
        if attention != "latent":
            return False
        widths, page_size = latent_widths, pool_shapes[0][2]
    elif kind is None:
        return any(prefill_in_kernel(attention, attn_kinds, latent_widths,
                                     pool_shapes, t_len, n_pages, seen, i)
                   for i in range(len(attn_kinds)))
    elif _is_latent(attn_kinds[kind]):
        widths = latent_widths
        page_size = pool_shapes[attn_kinds[kind]["pools"][0]][2]
    else:
        spec = attn_kinds[kind]
        mine = [pool_shapes[i] for i in spec["pools"]]
        if (spec.get("mixer", attention) != "gqa"
                or spec["window"] is not None or _keeps_state(spec)
                or len(mine) != 2
                or any(len(shape) != 4 for shape in mine)):
            return False
        widths, page_size = (mine[1][3] // spec["n_kv"],), mine[1][2]
    kb = page_size * _pages_a_block(
        True, page_size, _pages_seen(n_pages, seen, page_size), 1)
    return _pa.prefill_fold_usable(t_len, kb, *widths)


def _make_paged_runner(params, emb_w, fnorm, head, *, n_heads, n_kv,
                       base, eps, page_size, head_scale=None,
                       moe_top_k=2, kinds=None, lead=None, stacks=None):
    return _PagedRunner(params, emb_w, fnorm, head, n_heads=n_heads,
                        n_kv=n_kv, base=base, eps=eps,
                        page_size=page_size, head_scale=head_scale,
                        moe_top_k=moe_top_k, kinds=kinds, lead=lead,
                        stacks=stacks)


def _paged_model_inputs(ins, prefix=""):
    """(params, emb, fnorm, head, head_scale) from a paged op's input
    slots, honoring int8 <Slot>Scale companions; ``prefix`` selects the
    draft model's slots in llama_paged_spec_step."""
    params = {s: ins[prefix + s][0] for s in _STACK_SLOTS
              if prefix + s in ins}
    for s in _MATMUL_SLOTS:
        if prefix + s + "Scale" in ins:
            params[s + "Scale"] = ins[prefix + s + "Scale"][0]
    head_scale = (ins[prefix + "LmHeadScale"][0]
                  if prefix + "LmHeadScale" in ins else None)
    return (params, ins[prefix + "Emb"][0], ins[prefix + "FinalNorm"][0],
            ins[prefix + "LmHead"][0], head_scale)


def _llama_runner(ins, attrs):
    params, emb_w, fnorm, head, head_scale = _paged_model_inputs(ins)
    return _make_paged_runner(
        params, emb_w, fnorm, head, n_heads=attrs["n_heads"],
        n_kv=attrs.get("n_kv_heads", attrs["n_heads"]),
        base=attrs.get("rope_base", 10000.0),
        eps=attrs.get("epsilon", 1e-6),
        page_size=attrs["page_size"], head_scale=head_scale)


def _paged_prefill(run, tokens, lens, offsets, table, pools):
    """A window of each row's prompt into its pages: the body of every
    paged prefill op; ``offsets`` None: the window is the whole prompt,
    from position 0. Returns (next token [B], its float32 logits
    [B, V], the pools); ``run.picks`` then holds the routed layers'
    picks at each row's last real token."""
    b, t = tokens.shape
    run.lens, run.pick_at = lens, lens - 1
    # what Stats counts: real tokens of rows that own a real first page
    run.valid = (jnp.arange(t, dtype=jnp.int32)[None] < lens[:, None]) \
        & (table[:, :1] > 0)
    run.fresh = offsets is None
    if offsets is None:         # and sees its own window, no further
        run.seen, offsets = t, jnp.zeros((b,), jnp.int32)
    h, *pools = run.forward(run.embed(tokens), *pools, table, offsets, t)
    logits = run.logits_of(h[jnp.arange(b), lens - 1])
    return jnp.argmax(logits, axis=-1).astype(tokens.dtype), logits, pools


def _paged_decode(run, tok, pos, table, pools, steps, extras=False):
    """``steps`` greedy steps of every slot: the body of every paged
    decode op. The steps carry the pools themselves (a window kind's: the
    view of its rings, ``open_rings`` / ``close_rings``) and each layer
    writes its entry where it lies; what comes back is what was carried.
    Returns (tokens [B, steps], pools) and, with ``extras``, each step's
    float32 logits [B, steps, V], its routed picks [B, steps, routed
    layers, K] and the dispatch's Stats."""
    pos = pos.astype(jnp.int32)
    cache = run.open_rings(pools)
    run.valid = table[:, :1] > 0        # a live row owns a real first page

    def step(carry, _):
        tok, pos, cache, stats = carry
        h, *cache = run.decode_step(run.embed(tok[:, None]), *cache, table,
                                    pos)
        logits = run.logits_of(h[:, 0])
        if extras and run.head.dtype == jnp.bfloat16:
            # the logits handed back are the ones compared, whether a
            # caller fetches them or not: the head's product rounded to
            # its own type, SAID to the compiler. Where nobody fetches
            # them it fuses the argmax into the head and would keep the
            # product's excess precision, and ties between rounded
            # logits then fall another way than in the form that stores
            # them (PERF.md section 6, PR 59)
            logits = jax.lax.reduce_precision(logits, 8, 7)
        nxt = jnp.argmax(logits, axis=-1).astype(tok.dtype)
        if not extras:
            return (nxt, pos + 1, tuple(cache), stats), nxt
        return ((nxt, pos + 1, tuple(cache),
                 stats + run.stats(True, pos)),
                (nxt, logits, _picks_of(run, tok.shape[0])))

    stats0 = jnp.zeros_like(run.stats(False)) if extras else None
    (_, _, cache, stats), ys = jax.lax.scan(
        step, (tok, pos, cache, stats0), None, length=steps)
    pools = run.close_rings(pools, cache, pos, steps)
    if not extras:
        return jnp.moveaxis(ys, 0, 1), pools
    return (jnp.moveaxis(ys[0], 0, 1), pools, jnp.moveaxis(ys[1], 0, 1),
            jnp.moveaxis(ys[2], 0, 1), stats)


@register_op("llama_paged_prefill")
def _llama_paged_prefill(ctx, ins, attrs):
    """Prefill one (or a few) prompt(s) into paged-KV slots and emit
    the first greedy token per row.

    Tokens [B, T_bucket] int (end-padded to the bucket — pad KV lands
    at positions >= Lens and is overwritten write-before-attend by the
    decode steps that later claim those positions); Lens [B] real
    prompt lengths; Table [B, max_pages] page indices; KPages/VPages
    [L, n_pages, page_size, g, hd]. Outputs NextTok [B] plus the
    updated pools."""
    tokens = ins["Tokens"][0]
    nxt, _, (kp, vp) = _paged_prefill(
        _llama_runner(ins, attrs), tokens, ins["Lens"][0], None,
        ins["Table"][0], (ins["KPages"][0], ins["VPages"][0]))
    return {"NextTok": [nxt], "KPagesOut": [kp], "VPagesOut": [vp]}


@register_op("llama_paged_prefill_chunk")
def _llama_paged_prefill_chunk(ctx, ins, attrs):
    """Prefill ONE SLICE of a prompt into paged-KV slots at an
    arbitrary per-row offset — the chunked-prefill kernel: a long
    prompt is admitted as decode-step-sized slices so its prefill
    co-schedules with other requests' decode steps instead of
    stalling them.

    Tokens [B, C] int (the slice, end-padded to the chunk width C);
    Lens [B] real token counts in THIS slice; Offsets [B] int32 the
    absolute position of each row's first slice token; Table
    [B, max_pages]; KPages/VPages [L, n_pages, page_size, g, hd].

    Bit-parity contract (pinned by tests/test_slo_sched.py): the math
    is exactly ``llama_paged_prefill``'s forward with ``pos0 =
    Offsets`` instead of zeros. Every position's KV depends only on
    positions <= itself (causal mask with exact softmax zeros beyond
    each query's own position), so filling [0, C), then [C, 2C), ...
    writes bitwise the same pool values as one whole-prompt pass —
    same einsum shapes, same reduction windows, same dtypes. Pad
    positions >= Offsets+Lens land garbage KV that the NEXT chunk (or
    the first decode step) overwrites write-before-attend, the same
    discipline the whole-prompt op already relies on.

    NextTok [B] is the greedy token after the last REAL slice
    position — meaningful only on a prompt's final chunk (earlier
    chunks' callers discard it)."""
    nxt, _, (kp, vp) = _paged_prefill(
        _llama_runner(ins, attrs), ins["Tokens"][0], ins["Lens"][0],
        ins["Offsets"][0].astype(jnp.int32), ins["Table"][0],
        (ins["KPages"][0], ins["VPages"][0]))
    return {"NextTok": [nxt], "KPagesOut": [kp], "VPagesOut": [vp]}


@register_op("llama_paged_decode")
def _llama_paged_decode(ctx, ins, attrs):
    """``steps`` greedy decode steps over the paged KV pool, all slots
    in lockstep — ONE executable per (model, max_batch, steps) that
    never recompiles as requests churn through the slots.

    Tokens [B]: each row's last emitted (not yet cached) token;
    Positions [B]: the absolute position that token will occupy (== the
    row's current cache length). Inactive slots feed token 0, position
    1, and an all-null table; their outputs are garbage the engine
    discards, and their writes land on the null page. OutTokens
    [B, steps]."""
    toks, (kp, vp) = _paged_decode(
        _llama_runner(ins, attrs), ins["Tokens"][0], ins["Positions"][0],
        ins["Table"][0], (ins["KPages"][0], ins["VPages"][0]),
        max(1, int(attrs.get("steps", 1))))
    return {"OutTokens": [toks], "KPagesOut": [kp], "VPagesOut": [vp]}


# ---------------------------------------------------------------------
# The same three programs for a model whose block kinds are attributes
# (models/latent_moe.py): one list of pools, whatever a token's cache
# entries are; the float32 logits behind each emitted token and the
# dispatch's counters (PAGED_STATS) beside the tokens.
# ---------------------------------------------------------------------

_BLOCK_SLOTS = (
    "AttnNorm", "MlpNorm", "Wqa", "QNorm", "Wqb", "Wkva", "KvNorm", "Wkvb",
    "Wo", "WGate", "WUp", "WDown", "MoeRouter", "MoeBias", "MoeWGate",
    "MoeWUp", "MoeWDown", "ShWGate", "ShWUp", "ShWDown", "HcAttnPhi",
    "HcAttnAlpha", "HcAttnBias", "HcMlpPhi", "HcMlpAlpha", "HcMlpBias",
    "Wq", "Wk", "Wv", "Sink", "WIn", "ConvW", "ConvB", "WX", "DtNorm",
    "BNorm", "CNorm", "WDt", "DtBias", "ALog", "D", "WOut",
    "AttnPostNorm", "MlpPostNorm", "KNorm", "Wz", "Wa", "Wb", "GNorm",
    "Wg")


def _block_runner(ins, attrs):
    """The runner of a block_paged_* op: the stacked layers' parameters
    by slot, the leading dense layers' under ``Lead<Slot>``, and the
    kinds from the attributes; where these mix attention kinds
    (``attn_kinds``), each kind's stacked layers under ``<its
    stack><Slot>`` and the window kinds' table under ``RingTable``; a
    looped model's exit gate under ``ExitW`` / ``ExitB``."""
    kinds = BlockKinds(
        n_heads=attrs["n_heads"], n_kv=attrs.get("n_kv"),
        base=attrs.get("rope_base", 10000.0), eps=attrs["epsilon"],
        attention=attrs["attention"], ffn=attrs["ffn"],
        residual=attrs["residual"], moe_top_k=attrs["moe_top_k"],
        scoring=attrs["scoring"], route_scale=attrs["route_scale"],
        n_group=attrs["n_group"], topk_group=attrs["topk_group"],
        experts_first=attrs["experts_first"], kv_rank=attrs["kv_rank"], rope_dim=attrs["rope_dim"],
        nope_dim=attrs["nope_dim"], v_dim=attrs["v_dim"],
        rope_inv_freq=np.asarray(attrs["rope_inv_freq"], np.float32),
        softmax_scale=attrs["softmax_scale"],
        n_streams=attrs["n_streams"],
        sinkhorn_iters=attrs["sinkhorn_iters"], hc_eps=attrs["hc_eps"],
        hc_clamp=attrs["hc_clamp"], key_dim=attrs.get("key_dim"),
        rotary_dim=attrs.get("rotary_dim"),
        value_scale=attrs.get("value_scale", 1.0),
        attn_kinds=attrs.get("attn_kinds"),
        layer_kinds=attrs.get("layer_kinds"),
        passes=attrs.get("passes", 1),
        route_eps=attrs.get("route_eps", 1e-20))
    params = {s: ins[s][0] for s in _BLOCK_SLOTS if s in ins}
    lead = {s: ins["Lead" + s][0] for s in _BLOCK_SLOTS
            if "Lead" + s in ins}
    stacks = {k["stack"]: {s: ins[k["stack"] + s][0] for s in _BLOCK_SLOTS
                           if k["stack"] + s in ins}
              for k in kinds.attn_kinds or ()}
    run = _make_paged_runner(
        params, ins["Emb"][0], ins["FinalNorm"][0], ins["LmHead"][0],
        n_heads=kinds.n_heads, n_kv=kinds.n_kv, base=kinds.base,
        eps=kinds.eps, page_size=attrs["page_size"],
        moe_top_k=kinds.moe_top_k, kinds=kinds, lead=lead or None,
        stacks=stacks or None)
    if "RingTable" in ins:
        run.ring_table = ins["RingTable"][0]
    if "StateTable" in ins:
        run.state_table = ins["StateTable"][0]
    if "ExitW" in ins:
        run.exit_gate = (ins["ExitW"][0], ins["ExitB"][0])
    return run


def _picks_of(run, rows):
    """The routed layers' picks of the last forward, rows first; [rows, 0,
    K] for a model without a routed layer."""
    if run.picks is None:
        return jnp.zeros((rows, 0, run.kinds.moe_top_k), jnp.int32)
    return jnp.moveaxis(run.picks, 0, 1)


def _block_prefill_outputs(run, nxt, logits, pools):
    return {"NextTok": [nxt], "Logits": [logits],
            "Picks": [_picks_of(run, nxt.shape[0])],
            "PoolsOut": list(pools), "Stats": [run.stats(False)]}


@register_op("block_paged_prefill")
def _block_paged_prefill(ctx, ins, attrs):
    """llama_paged_prefill for a model of any block kinds: Pools is the
    list of its cache pools; also Logits [B, V] float32 (the logits
    NextTok is the argmax of), Picks [B, routed layers, K] (the experts
    each routed layer picked for the row's last real token: routing is
    discrete, and whoever compares Logits with a reference has to know
    it) and Stats (PAGED_STATS)."""
    run = _block_runner(ins, attrs)
    tokens = ins["Tokens"][0]
    return _block_prefill_outputs(run, *_paged_prefill(
        run, tokens, ins["Lens"][0], None, ins["Table"][0], ins["Pools"]))


@register_op("block_paged_prefill_chunk")
def _block_paged_prefill_chunk(ctx, ins, attrs):
    """llama_paged_prefill_chunk for a model of any block kinds (see
    block_paged_prefill)."""
    run = _block_runner(ins, attrs)
    return _block_prefill_outputs(run, *_paged_prefill(
        run, ins["Tokens"][0], ins["Lens"][0],
        ins["Offsets"][0].astype(jnp.int32), ins["Table"][0],
        ins["Pools"]))


@register_op("block_paged_decode")
def _block_paged_decode(ctx, ins, attrs):
    """llama_paged_decode for a model of any block kinds: also Logits
    [B, steps, V] float32, Picks [B, steps, routed layers, K] and Stats
    summed over the steps. Logits and Picks are for a caller that
    compares them with a reference: the serving loop fetches neither
    (models/latent_moe.py build_block_programs), and the compiler then
    builds neither."""
    toks, pools, logits, picks, stats = _paged_decode(
        _block_runner(ins, attrs), ins["Tokens"][0], ins["Positions"][0],
        ins["Table"][0], ins["Pools"],
        max(1, int(attrs.get("steps", 1))), extras=True)
    return {"OutTokens": [toks], "Logits": [logits], "Picks": [picks],
            "PoolsOut": list(pools), "Stats": [stats]}


@register_op("llama_paged_spec_step")
def _llama_paged_spec_step(ctx, ins, attrs):
    """One speculative round over the paged pools, PER-ROW acceptance
    (greedy): the draft proposes ``gamma`` tokens per slot, the target
    scores cur + all proposals in one [B, gamma+1] forward, and each
    row keeps its own longest accepted prefix — rows advance at their
    own acceptance rate instead of the fused op's batch-lockstep
    minimum, because positions are per-slot here anyway.

    The draft's first window reprocesses [Prev, Tokens] at pos-1..pos:
    when the prior round accepted everything, the draft never cached
    its own last proposal, and reprocessing Prev fills that hole
    (idempotent when no hole exists — same token, same position, same
    visible prefix). Emitted [B, gamma+1] holds the greedy target
    token after each window position; Accepted [B] (= per-row m+1)
    says how many leading entries are valid. Stale rejected KV sits at
    positions >= pos + Accepted and is rewritten before any later
    query can attend it (write-before-attend + the length mask)."""
    cur = ins["Tokens"][0]
    prev = ins["Prev"][0]
    pos = ins["Positions"][0].astype(jnp.int32)
    table = ins["Table"][0]
    tkp, tvp = ins["KPages"][0], ins["VPages"][0]
    dkp, dvp = ins["DraftKPages"][0], ins["DraftVPages"][0]
    t_params, emb_w, fnorm, head, t_hscale = _paged_model_inputs(ins)
    d_params, demb, dfnorm, dhead, d_hscale = \
        _paged_model_inputs(ins, prefix="Draft")
    page_size = attrs["page_size"]
    gamma = max(1, int(attrs.get("gamma", 4)))
    t_run = _make_paged_runner(
        t_params, emb_w, fnorm, head, n_heads=attrs["n_heads"],
        n_kv=attrs.get("n_kv_heads", attrs["n_heads"]),
        base=attrs.get("rope_base", 10000.0),
        eps=attrs.get("epsilon", 1e-6), page_size=page_size,
        head_scale=t_hscale)
    d_run = _make_paged_runner(
        d_params, demb, dfnorm, dhead, n_heads=attrs["draft_n_heads"],
        n_kv=attrs.get("draft_n_kv_heads", attrs["draft_n_heads"]),
        base=attrs.get("draft_rope_base",
                       attrs.get("rope_base", 10000.0)),
        eps=attrs.get("draft_epsilon", attrs.get("epsilon", 1e-6)),
        page_size=page_size, head_scale=d_hscale)

    # the round runs against the pools: its windows of several tokens
    # through ``forward`` (a window's entries into its pages, each layer's
    # pages of the row attended), the draft's single steps through the
    # decode step. A row near the end of its table runs past ``kmax``:
    # those positions are dropped
    d_run.leaves_table = t_run.leaves_table = True

    # 1. draft proposes gamma tokens autoregressively per row
    dh, dkp, dvp = d_run.forward(
        demb[jnp.stack([prev, cur], axis=1)], dkp, dvp, table, pos - 1, 2)
    dl = d_run.logits_of(dh[:, 1])
    drafts = []
    d_tok = None
    for i in range(gamma):
        if i > 0:
            dh, dkp, dvp = d_run.decode_step(
                demb[d_tok][:, None], dkp, dvp, table, pos + i)
            dl = d_run.logits_of(dh[:, 0])
        d_tok = jnp.argmax(dl, axis=-1).astype(cur.dtype)
        drafts.append(d_tok)
    D = jnp.stack(drafts, axis=1)                        # [B, gamma]

    # 2. target scores cur + all gamma proposals in ONE forward
    cand = jnp.concatenate([cur[:, None], D], axis=1)    # [B, gamma+1]
    th, tkp, tvp = t_run.forward(emb_w[cand], tkp, tvp, table, pos,
                                 gamma + 1)
    G = jnp.argmax(t_run.logits_of(th), axis=-1).astype(cur.dtype)

    # 3. per-row longest accepted prefix; row b's emission is
    # G[b, :m_b + 1] (m_b accepted drafts + the correction/bonus)
    match = (D == G[:, :gamma]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    return {"Emitted": [G], "Accepted": [(m + 1).astype(jnp.int32)],
            "KPagesOut": [tkp], "VPagesOut": [tvp],
            "DraftKPagesOut": [dkp], "DraftVPagesOut": [dvp]}


@register_op("llama_decoder_stack")
def _llama_decoder_stack(ctx, ins, attrs):
    """The whole decoder-layer stack as ONE op with layer-stacked weights
    (leading [L] axis): [rms_norm → GQA attention (rope, flash kernel) →
    rms_norm → SwiGLU] × L.

    TPU-first rationale: stacking the per-layer weights makes the layer
    loop a ``lax.scan`` (one compiled block, not L copies), and makes
    pipeline parallelism a *data layout* question — reshape the stack to
    [n_stages, L/n_stages, ...], shard the stage axis over the mesh 'pp'
    axis, and run the GPipe ppermute schedule (parallel/pipeline.py).
    This replaces the reference's section-based pipeline trainer
    (reference paddle/fluid/operators/ send/recv lineage) with a single
    SPMD program. Dispatch: 'pp' in the active mesh → gpipe; else scan.
    """
    x = ins["X"][0]                                     # [B, T, D]
    _reject_quant_scales(ins, "llama_decoder_stack")
    params = {s: ins[s][0] for s in _STACK_SLOTS}
    n_heads = attrs["n_heads"]
    n_kv = attrs.get("n_kv_heads", n_heads)
    base = attrs.get("rope_base", 10000.0)
    eps = attrs.get("epsilon", 1e-6)
    n_micro = attrs.get("n_micro", 0)
    blk = make_flash_block(n_heads, n_kv, base, eps,
                           attrs.get("remat", True))

    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    pp = mesh.axes.get("pp", 1) if mesh is not None else 1
    n_layers = params["Wq"].shape[0]
    if pp <= 1:
        # scan_unroll replicates k layer bodies per scan iteration:
        # fewer loop iterations (their overhead is not re-measured on
        # this installation) at the cost of a k-times-larger
        # executable to compile
        out, _ = jax.lax.scan(
            lambda h, p: (blk(p, h), None), x, params,
            unroll=max(1, int(attrs.get("scan_unroll", 1))))
    else:
        if n_layers % pp:
            raise ValueError(
                f"llama_decoder_stack: {n_layers} layers do not split "
                f"over the mesh 'pp' axis of size {pp}")
        from ..parallel.pipeline import gpipe
        per_stage = n_layers // pp
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((pp, per_stage) + a.shape[1:]), params)

        def stage_fn(sp, h):
            return jax.lax.scan(lambda c, p: (blk(p, c), None), h, sp)[0]

        nm = int(n_micro) or pp
        b = x.shape[0]
        if b % nm:
            raise ValueError(
                f"llama_decoder_stack: batch {b} is not divisible by "
                f"n_micro={nm} microbatches")
        dp = mesh.axes.get("dp", 1)
        if (b // nm) % dp:
            raise ValueError(
                f"llama_decoder_stack: microbatch {b // nm} "
                f"(batch {b} / n_micro {nm}) is not divisible by the "
                f"mesh 'dp' axis of size {dp}")
        micro = x.reshape((nm, b // nm) + x.shape[1:])
        piped = gpipe(stage_fn, mesh, checkpoint_stages=False)
        out = piped(stacked, micro).reshape(x.shape)
    return {"Out": [out]}


# ---------------------------------------------------------------------
# Numerics transfer rules (analysis/numcheck.py) for the paged serving
# ops. Same purity contract as ops/basic.py's rules: interval
# arithmetic only, no jax. Token outputs are argmax INDICES — exact
# non-negative integers regardless of activation magnitude — and the
# page pools stay finite whenever their inputs are finite (every write
# is a projection/softmax mix of finite operands; masked lanes get
# exact softmax zeros, never inf arithmetic). The engine consumes only
# the slots each op actually declares, so one shared rule covers the
# whole prefill/chunk/decode/spec family.
# ---------------------------------------------------------------------

from ..analysis.numcheck import NumInfo, num_first  # noqa: E402
from ..core.registry import register_numerics  # noqa: E402


def _num_paged_kv(op, ins, attrs):
    tok = NumInfo(0.0, math.inf, finite=True, confident=True)
    out = {"NextTok": [tok], "OutTokens": [tok], "Emitted": [tok],
           "Accepted": [NumInfo(0.0, math.inf, finite=True,
                                confident=True)]}
    for slot, src in (("KPagesOut", "KPages"), ("VPagesOut", "VPages"),
                      ("DraftKPagesOut", "DraftKPages"),
                      ("DraftVPagesOut", "DraftVPages")):
        pool = num_first(ins, src)
        out[slot] = [NumInfo(-math.inf, math.inf, finite=pool.finite,
                             confident=pool.confident)]
    return out


def _num_block_paged(op, ins, attrs):
    count = NumInfo(0.0, math.inf, finite=True, confident=True)
    pool = num_first(ins, "Pools")
    unknown = NumInfo(-math.inf, math.inf, finite=pool.finite,
                      confident=pool.confident)
    return {"NextTok": [count], "OutTokens": [count], "Picks": [count],
            "Stats": [count], "Logits": [unknown],
            "PoolsOut": [unknown] * len(ins.get("Pools", ()))}


for _op in ("block_paged_prefill", "block_paged_prefill_chunk",
            "block_paged_decode"):
    register_numerics(_op)(_num_block_paged)
register_numerics("llama_paged_prefill")(_num_paged_kv)
register_numerics("llama_paged_prefill_chunk")(_num_paged_kv)
register_numerics("llama_paged_decode")(_num_paged_kv)
register_numerics("llama_paged_spec_step")(_num_paged_kv)
