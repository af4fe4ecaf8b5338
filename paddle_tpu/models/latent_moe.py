"""Decoder-only models whose block is latent attention with routed
experts, for serving: Xing4.0-29B-A4B's block (hyper-connection
residuals, every expert held) and DeepSeek-V3's (plain residuals, a
group-limited router, and a share of the experts) are both values of
``LatentMoEConfig``.

The block is ops/transformer_ops.py ``block_forward`` at these kinds:
multi-head latent attention whose cache holds one ``[kv_rank +
rope_dim]`` entry a token a layer (DeepSeek-V2, arXiv:2405.04434) with
YaRN-scaled rotary positions; sigmoid-scored routed experts with a
selection bias, optionally group-limited (DeepSeek-V3, arXiv:2412.19437),
and a shared expert, drop-free (ops/moe.py); the residual path either
plain (``x + F(norm(x))``) or widened to ``n_streams`` and mixed by
manifold-constrained hyper-connections (arXiv:2512.24880). The first
``n_dense_layers`` have a dense SwiGLU in the experts' place.

A model may be ONE CHIP'S SHARE of an expert-parallel deployment: the
router stays ``router_width`` wide and every token picks ``moe_top_k``
of all of them, while the chip holds ``n_experts`` of them from
``experts_first`` on and computes their part of the sum. What the absent
experts would add is left out (no exchange is run, nothing stands in for
the other chips), and that partial result goes on to the next layer.

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill,
chunk and decode programs; there is no training graph, no fused
generator and no speculative form (the published multi-token-prediction
layers are left out).
"""
from dataclasses import dataclass

from .. import layers
from ..layers import transformer as tfl
from ..ops.transformer_ops import (PAGED_STATS, decode_experts_in_kernel,
                                   decode_in_place,
                                   prefill_experts_in_kernel,
                                   prefill_in_kernel, state_step_in_kernel,
                                   whole_tiles, yarn_inv_freq, yarn_mscale)
from .llama import (PagedDecodePrograms, cache_pool_specs,
                    prefill_buckets_reached)

__all__ = ["LatentMoEConfig", "LATENT_MOE_TINY", "LATENT_SHARE_TINY",
           "build_block_programs"]


@dataclass
class LatentMoEConfig:
    name: str = "latent-moe"
    vocab_size: int = 131072
    dim: int = 3584
    n_layers: int = 40
    n_dense_layers: int = 2
    n_heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    ffn_hidden: int = 9216           # the leading dense layers' SwiGLU
    n_experts: int = 64              # routed experts held
    router_width: int = None         # experts routed over (None: those held)
    experts_first: int = 0           # the first expert held
    n_group: int = 1                 # group-limited selection (moe_route)
    topk_group: int = 1
    moe_top_k: int = 4
    expert_hidden: int = 1024
    n_shared: int = 1
    route_scale: float = 2.0
    residual: str = "mhc"            # or "plain": one stream, no Hc*
    n_streams: int = 4               # hc_mult
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    norm_eps: float = 1e-6
    rope_base: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.router_width is None:
            self.router_width = self.n_experts
        if not 0 <= self.experts_first \
                <= self.router_width - self.n_experts:
            raise ValueError(
                f"{self.name}: experts {self.experts_first} to "
                f"{self.experts_first + self.n_experts - 1} are not "
                f"among a router's {self.router_width}")
        if self.router_width % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.name}: {self.router_width} experts do not make "
                f"{self.n_group} equal groups of which "
                f"{self.topk_group} are kept")

    @property
    def entry_dim(self):
        """Values a token leaves in a layer's cache."""
        return self.kv_rank + self.rope_dim

    @property
    def stored_dim(self):
        """The width the cache keeps an entry at: whole lane tiles,
        ``[latent | rotated key | zeros]`` (512 + 64 is stored 640 wide).
        A pool 576 wide is 4.5 tiles: the chip re-laid all of it three
        times a decode program and twice a prefill (PERF.md section 6,
        PR 34). The weights keep their published shapes."""
        return whole_tiles(self.entry_dim)

    def cache_spec(self):
        """A token's cache entries in one layer as the pools store them:
        [(shape, dtype)]."""
        return [((self.stored_dim,), self.dtype)]

    def softmax_scale(self):
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def block_attrs(self, page_size):
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "latent", "ffn": "routed",
            "residual": self.residual,
            "moe_top_k": self.moe_top_k, "scoring": "sigmoid",
            "route_scale": self.route_scale, "n_group": self.n_group,
            "topk_group": self.topk_group,
            "experts_first": self.experts_first, "kv_rank": self.kv_rank,
            "rope_dim": self.rope_dim, "nope_dim": self.nope_dim,
            "v_dim": self.v_dim,
            "rope_inv_freq": [float(x) for x in yarn_inv_freq(
                self.rope_dim, self.rope_base, self.rope_factor,
                self.rope_original_max, self.rope_beta_fast,
                self.rope_beta_slow)],
            "softmax_scale": self.softmax_scale(),
            "n_streams": self.n_streams,
            "sinkhorn_iters": self.sinkhorn_iters, "hc_eps": self.hc_eps,
            "hc_clamp": [float(x) for x in self.hc_clamp],
            "page_size": int(page_size)}

    def layer_params(self, n_layers, routed):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        with a routed (else dense) feed-forward. The router (as wide as
        ``router_width``, beside ``n_experts`` held experts), its bias
        and the hyper-connection coefficients (``mhc`` alone) are float32
        whatever ``dtype`` is."""
        L, D, H, n = n_layers, self.dim, self.n_heads, self.n_streams
        dt, mix = self.dtype, 2 * n + n * n
        out = {
            "AttnNorm": ("attn_norm", [L, D], dt),
            "MlpNorm": ("mlp_norm", [L, D], dt),
            "Wqa": ("wqa", [L, D, self.q_rank], dt),
            "QNorm": ("q_norm", [L, self.q_rank], dt),
            "Wqb": ("wqb", [L, self.q_rank,
                            H * (self.nope_dim + self.rope_dim)], dt),
            "Wkva": ("wkva", [L, D, self.entry_dim], dt),
            "KvNorm": ("kv_norm", [L, self.kv_rank], dt),
            "Wkvb": ("wkvb", [L, self.kv_rank,
                              H * (self.nope_dim + self.v_dim)], dt),
            "Wo": ("wo", [L, H * self.v_dim, D], dt)}
        for which in ("Attn", "Mlp") if self.residual == "mhc" else ():
            low = which.lower()
            out["Hc" + which + "Phi"] = (f"hc_{low}_phi", [L, n * D, mix],
                                         "float32")
            out["Hc" + which + "Alpha"] = (f"hc_{low}_alpha", [L, 3],
                                           "float32")
            out["Hc" + which + "Bias"] = (f"hc_{low}_bias", [L, mix],
                                          "float32")
        if not routed:
            F = self.ffn_hidden
            out.update(WGate=("w_gate", [L, D, F], dt),
                       WUp=("w_up", [L, D, F], dt),
                       WDown=("w_down", [L, F, D], dt))
            return out
        E, F, S = self.n_experts, self.expert_hidden, \
            self.n_shared * self.expert_hidden
        R = self.router_width
        out.update(MoeRouter=("moe_router", [L, D, R], "float32"),
                   MoeBias=("moe_bias", [L, R], "float32"),
                   MoeWGate=("moe_w_gate", [L, E, D, F], dt),
                   MoeWUp=("moe_w_up", [L, E, D, F], dt),
                   MoeWDown=("moe_w_down", [L, E, F, D], dt))
        if S:
            out.update(ShWGate=("sh_w_gate", [L, D, S], dt),
                       ShWUp=("sh_w_up", [L, D, S], dt),
                       ShWDown=("sh_w_down", [L, S, D], dt))
        return out

    def param_shapes(self):
        """Every parameter the programs read from the scope: name ->
        (shape, dtype). ``blocks.*`` are the routed layers, stacked;
        ``lead.*`` the leading dense ones."""
        out = {"tok_emb": ([self.vocab_size, self.dim], self.dtype),
               "final_norm": ([self.dim], self.dtype),
               "lm_head": ([self.dim, self.vocab_size], self.dtype)}
        for scope_name, n, routed in (
                ("lead", self.n_dense_layers, False),
                ("blocks", self.n_layers - self.n_dense_layers, True)):
            if n:
                for suffix, shape, dt in self.layer_params(
                        n, routed).values():
                    out[f"{scope_name}.{suffix}"] = (shape, dt)
        return out

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model: as
        models/llama.py build_llama_paged_programs, over ONE pool of
        ``[n_layers, n_pages, page_size, stored_dim]``, each
        program also returning its float32 logits, the experts its routed
        layers picked for the tokens those logits belong to, and
        PAGED_STATS. The
        scope must already hold ``param_shapes()``."""
        if draft_cfg is not None:
            raise NotImplementedError(
                f"{self.name}: latent-attention models have no "
                "speculative paged form (the multi-token-prediction "
                "layer is not built); drop draft_cfg")
        if quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published; the "
                "int8 path (qmat) covers the dense Llama block only; "
                "drop quantize")
        if self.n_layers <= self.n_dense_layers:
            raise ValueError("no routed layer after the dense ones")
        return build_block_programs(
            self, pool_specs=cache_pool_specs(self, n_pages, page_size),
            common=dict(
                params=self.layer_params(
                    self.n_layers - self.n_dense_layers, True),
                lead_params=(self.layer_params(self.n_dense_layers, False)
                             if self.n_dense_layers else {}),
                attrs=self.block_attrs(page_size),
                vocab_size=self.vocab_size, dtype=self.dtype),
            max_batch=max_batch, page_size=page_size, n_pages=n_pages,
            pages_per_seq=pages_per_seq, prompt_buckets=prompt_buckets,
            decode_block=decode_block, chunk_size=chunk_size)


def build_block_programs(cfg, *, pool_specs, common, max_batch, page_size,
                         n_pages, pages_per_seq, prompt_buckets,
                         decode_block, chunk_size, kinds=None,
                         stats=PAGED_STATS):
    """The prefill, chunk and decode programs of a model whose block
    kinds are attributes (layers/transformer.py block_paged_op with
    ``common``), over the pools ``pool_specs``. ``kinds``: the model's
    cache kinds beyond ``sequence`` as PagedDecodePrograms carries them,
    name -> spec, in the order of their tables (``window``:
    models/hybrid_moe.py; ``state``: models/hybrid_ssm.py); every program
    then takes a table a kind, [rows, spec["pages_per_seq"]], behind the
    page table, under the op's slot and the feed name ``spec["table"]``
    gives. A bundle's ``fetch`` is what the engine's loop dispatches and
    ``extras`` names what it fetches behind tokens and pools; the decode
    bundle also has ``probe``, its whole fetch set under the same two
    keys, for a caller outside the loop."""
    from ..core import framework

    def bundle(kind, prefix, feeds, steps=1):
        """One program: ``feeds`` are (slot, feed name, shape, dtype)
        of its data inputs, in feed order; the pools follow. What it
        fetches beside tokens and pools: logits, picks and stats."""
        main = framework.Program()
        with framework.program_guard(main, framework.Program()), \
                framework.unique_name.guard():
            data = {slot: layers.data(
                name=f"{prefix}_{fname}", shape=list(shape),
                dtype=dt, append_batch_size=False)
                for slot, fname, shape, dt in feeds}
            pools = [layers.data(
                name=f"{prefix}_pool" + (str(i) if i else ""),
                shape=shape, dtype=dt, append_batch_size=False)
                for i, (shape, dt) in enumerate(pool_specs)]
            out, pools_out, logits, picks, st = tfl.block_paged_op(
                kind, data, pools, steps=steps, stats=stats, **common)
        return {"program": main.clone(for_test=True),
                "feeds": tuple(f"{prefix}_{f[1]}" for f in feeds)
                + tuple(p.name for p in pools),
                "fetch": [out] + pools_out + [logits, picks, st],
                "extras": ("logits", "picks", "stats")}

    def tables(b):
        return [("Table", "table", [b, pages_per_seq], "int32")] + [
            (*spec["table"], [b, spec["pages_per_seq"]], "int32")
            for spec in (kinds or {}).values()]

    attrs = common["attrs"]
    shapes = [shape for shape, _ in pool_specs]

    def attn_in_kernel(t_len, seen):
        """Whether a prefill program over a ``t_len``-token window attends
        through the kernel: asked as its op asks where it lowers."""
        return prefill_in_kernel(
            attrs["attention"], attrs.get("attn_kinds"),
            (attrs["nope_dim"], attrs["v_dim"]), shapes, t_len,
            pages_per_seq, seen)

    param_tables = [common["params"], common["lead_params"]] + [
        table for _, _, table in common.get("stacks", ())]

    def experts_in_kernel(t_len):
        """Whether its routed layers' pairs go through the grouped kernel:
        asked as ``moe_apply_sorted`` asks where it lowers."""
        return prefill_experts_in_kernel(param_tables, t_len)

    prefill = {
        bucket: dict(bundle("prefill", "pp", [
            ("Tokens", "tokens", [1, bucket], "int64"),
            ("Lens", "lens", [1], "int32"), *tables(1)]),
            attn_in_kernel=attn_in_kernel(bucket, bucket),
            experts_in_kernel=experts_in_kernel(bucket))
        for bucket in prefill_buckets_reached(prompt_buckets,
                                              chunk_size)}
    decode = bundle("decode", "dc", [
        ("Tokens", "tokens", [max_batch], "int64"),
        ("Positions", "positions", [max_batch], "int32"),
        *tables(max_batch)], steps=decode_block)
    # two fetch sets over the one Program, an executable each (the
    # executor keys them by the fetch names). ``probe``: everything, for a
    # caller that compares the logits [max_batch, decode_block, vocab] of
    # every step with a reference. The serving loop's own fetches tokens,
    # pools and stats: no request receives those logits, and stacked as a
    # scan's output they cost a pass over the whole float32 buffer a step
    # (PERF.md section 6, PR 59); the compiler drops an output nobody
    # fetches, and the picks go with them
    *head, _, _, st = decode["fetch"]
    decode.update(probe={"fetch": decode["fetch"],
                         "extras": decode["extras"]},
                  fetch=head + [st], extras=("stats",))
    decode["in_place"] = decode_in_place(
        attrs["attention"], attrs.get("attn_kinds"), shapes)
    decode["state_in_kernel"] = state_step_in_kernel(
        attrs.get("attn_kinds"), pool_specs)
    decode["experts_in_kernel"] = decode_experts_in_kernel(
        param_tables, max_batch)
    chunk = None
    if chunk_size is not None:
        cs = int(chunk_size)
        if cs < 1:
            raise ValueError(f"chunk_size must be >= 1, got {cs}")
        chunk = bundle("prefill_chunk", "ck", [
            ("Tokens", "tokens", [1, cs], "int64"),
            ("Lens", "lens", [1], "int32"),
            ("Offsets", "offsets", [1], "int32"), *tables(1)])
        chunk["attn_in_kernel"] = attn_in_kernel(cs, None)
        chunk["experts_in_kernel"] = experts_in_kernel(cs)
    return PagedDecodePrograms(
        cfg, None, page_size, pages_per_seq, n_pages, max_batch,
        prefill, decode, None, list(pool_specs), None,
        chunk=chunk, chunk_size=None if chunk is None else cs,
        stats=stats, kinds=kinds)


LATENT_MOE_TINY = LatentMoEConfig(
    name="latent-moe-tiny", vocab_size=96, dim=32, n_layers=3,
    n_dense_layers=1, n_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
    rope_dim=8, v_dim=8, ffn_hidden=64, n_experts=8, moe_top_k=2,
    expert_hidden=16, n_shared=1, n_streams=4, sinkhorn_iters=20,
    rope_original_max=16, dtype="float32")

# one chip's share of a layer split over four: a 16-wide router in 4 groups
# of which 2 are kept, 3 experts a token, experts 4-7 held; plain residuals
LATENT_SHARE_TINY = LatentMoEConfig(
    name="latent-share-tiny", vocab_size=96, dim=32, n_layers=3,
    n_dense_layers=1, n_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
    rope_dim=8, v_dim=8, ffn_hidden=64, n_experts=4, router_width=16,
    experts_first=4, n_group=4, topk_group=2, moe_top_k=3,
    expert_hidden=16, n_shared=1, residual="plain", n_streams=1,
    sinkhorn_iters=0, rope_original_max=16, dtype="float32")
