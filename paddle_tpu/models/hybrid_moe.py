"""Decoder-only models that MIX TWO KINDS OF ATTENTION LAYER in one stack,
for serving: grouped-query attention whose layers are either ``full``
(every earlier position is seen) or ``window`` (the query and the
``window - 1`` positions before it, with a learned sink a head in the
softmax's denominator), each kind with its own key/value head count and
rotary base; keys wider than values, a part of each head rotated, the
values scaled; sigmoid-routed experts (ops/moe.py) on the plain residual
path behind leading dense layers. MiMo-V2-Flash's block is a value of
``HybridMoEConfig``.

The block is ops/transformer_ops.py ``block_forward`` at these kinds
(``gqa`` + ``routed`` + ``plain``), the kinds of attention layer being
DATA of ``BlockKinds`` (``attn_kinds``, ``layer_kinds``). What it asks of
the serving path is two CACHE KINDS under one engine: a full layer's
entries live as long as the sequence, a window layer's for ``window``
positions, so the model has two pairs of pools (``[full layers, pages,
page_size, n_kv_full * (key | value width)]`` and ``[window layers, ring
pages, page_size, n_kv_window * ...]``, a token's heads flat in its
page), two tables a row, and two stacks of layer parameters whose shapes
differ (``full.*``, ``window.*``; ``lead.*`` the leading dense layers).

As models/latent_moe.py, a model may be ONE CHIP'S SHARE of an
expert-parallel deployment (``router_width`` wide, ``n_experts`` held
from ``experts_first`` on), and it is serving only.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import HYBRID_STATS
from .latent_moe import build_block_programs

__all__ = ["HybridMoEConfig", "HYBRID_MOE_TINY"]

FULL, WINDOW = 0, 1         # a layer's kind, as ``layer_pattern`` has it


@dataclass
class HybridMoEConfig:
    name: str = "hybrid-moe"
    vocab_size: int = 152576
    dim: int = 4096
    layer_pattern: tuple = (0, 1, 1, 1, 1, 0)   # 0 full | 1 window, a layer
    n_dense_layers: int = 1          # leading layers with a dense SwiGLU
    n_heads: int = 64
    head_dim: int = 192              # queries and keys
    v_head_dim: int = 128
    n_kv_full: int = 4
    n_kv_window: int = 8
    rope_base_full: float = 5e6
    rope_base_window: float = 1e4
    rotary_dim: int = 64             # leading widths of a head rotated
    value_scale: float = 0.707
    window: int = 128                # the query and the window - 1 before
    sink_full: bool = False
    sink_window: bool = True
    ffn_hidden: int = 16384          # the leading dense layers' SwiGLU
    n_experts: int = 256             # routed experts held
    router_width: int = None         # experts routed over (None: those held)
    experts_first: int = 0
    moe_top_k: int = 8
    expert_hidden: int = 2048
    route_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_pattern = tuple(int(k) for k in self.layer_pattern)
        if self.router_width is None:
            self.router_width = self.n_experts
        if not 0 <= self.experts_first \
                <= self.router_width - self.n_experts:
            raise ValueError(
                f"{self.name}: experts {self.experts_first} to "
                f"{self.experts_first + self.n_experts - 1} are not "
                f"among a router's {self.router_width}")
        lead = self.layer_pattern[:self.n_dense_layers]
        if set(self.layer_pattern) - {FULL, WINDOW} or len(set(lead)) > 1 \
                or not self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"{self.name}: layer_pattern {self.layer_pattern} must "
                "hold 0 (full) and 1 (window) alone, its leading dense "
                "layers must be of one kind, and a routed layer must "
                "follow them")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"{self.name}: cannot rotate "
                             f"{self.rotary_dim} of {self.head_dim} widths")

    @property
    def n_layers(self):
        return len(self.layer_pattern)

    def layers_of(self, kind, routed=None):
        """How many layers are of attention kind ``kind`` (among the
        routed ones, or the leading dense ones, where ``routed`` says)."""
        p = self.layer_pattern
        if routed is not None:
            p = p[self.n_dense_layers:] if routed \
                else p[:self.n_dense_layers]
        return sum(1 for k in p if k == kind)

    def n_kv(self, kind):
        return self.n_kv_window if kind == WINDOW else self.n_kv_full

    def ring_pages(self, page_size):
        """Pages of a row's ring: the window, in whole pages."""
        return -(-self.window // page_size)

    def block_attrs(self, page_size):
        stack = {FULL: "Full", WINDOW: "Window"}
        attn_kinds = [
            {"name": "full", "n_kv": self.n_kv_full,
             "base": self.rope_base_full, "window": None,
             "sink": self.sink_full, "stack": stack[FULL],
             "pools": [0, 1]},
            {"name": "window", "n_kv": self.n_kv_window,
             "base": self.rope_base_window, "window": self.window,
             "sink": self.sink_window, "stack": stack[WINDOW],
             "pools": [2, 3]}]
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "gqa", "ffn": "routed", "residual": "plain",
            "moe_top_k": self.moe_top_k, "scoring": "sigmoid",
            "route_scale": self.route_scale, "n_group": 1,
            "topk_group": 1, "experts_first": self.experts_first,
            "kv_rank": 0, "rope_dim": 0, "nope_dim": 0,
            "v_dim": self.v_head_dim, "rope_inv_freq": [],
            "softmax_scale": None, "n_streams": 1, "sinkhorn_iters": 0,
            "hc_eps": 1e-6, "hc_clamp": [-30.0, 30.0],
            "key_dim": self.head_dim, "rotary_dim": self.rotary_dim,
            "value_scale": self.value_scale, "attn_kinds": attn_kinds,
            "layer_kinds": list(self.layer_pattern),
            "page_size": int(page_size)}

    def layer_params(self, n_layers, kind, routed):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        of attention kind ``kind`` with a routed (else dense)
        feed-forward. The router (``router_width`` wide beside
        ``n_experts`` held experts), its bias and the sinks are float32
        whatever ``dtype`` is."""
        L, D, H, G = n_layers, self.dim, self.n_heads, self.n_kv(kind)
        dt = self.dtype
        out = {
            "AttnNorm": ("attn_norm", [L, D], dt),
            "MlpNorm": ("mlp_norm", [L, D], dt),
            "Wq": ("wq", [L, D, H * self.head_dim], dt),
            "Wk": ("wk", [L, D, G * self.head_dim], dt),
            "Wv": ("wv", [L, D, G * self.v_head_dim], dt),
            "Wo": ("wo", [L, H * self.v_head_dim, D], dt)}
        if self.sink_window if kind == WINDOW else self.sink_full:
            out["Sink"] = ("sink", [L, H], "float32")
        if not routed:
            F = self.ffn_hidden
            out.update(WGate=("w_gate", [L, D, F], dt),
                       WUp=("w_up", [L, D, F], dt),
                       WDown=("w_down", [L, F, D], dt))
            return out
        E, F, R = self.n_experts, self.expert_hidden, self.router_width
        out.update(MoeRouter=("moe_router", [L, D, R], "float32"),
                   MoeBias=("moe_bias", [L, R], "float32"),
                   MoeWGate=("moe_w_gate", [L, E, D, F], dt),
                   MoeWUp=("moe_w_up", [L, E, D, F], dt),
                   MoeWDown=("moe_w_down", [L, E, F, D], dt))
        return out

    def stacks(self):
        """(slot prefix, scope name, kind, layers, routed) of every
        non-empty stack of layer parameters: the leading dense layers,
        then the routed layers of each attention kind."""
        out = [("Lead", "lead", self.layer_pattern[0],
                self.n_dense_layers, False)] if self.n_dense_layers else []
        out += [(prefix, scope, kind, self.layers_of(kind, True), True)
                for prefix, scope, kind in (("Full", "full", FULL),
                                            ("Window", "window", WINDOW))]
        return [s for s in out if s[3]]

    def param_shapes(self):
        """Every parameter the programs read from the scope: name ->
        (shape, dtype)."""
        out = {"tok_emb": ([self.vocab_size, self.dim], self.dtype),
               "final_norm": ([self.dim], self.dtype),
               "lm_head": ([self.dim, self.vocab_size], self.dtype)}
        for _, scope, kind, n, routed in self.stacks():
            for suffix, shape, dt in self.layer_params(
                    n, kind, routed).values():
                out[f"{scope}.{suffix}"] = (shape, dt)
        return out

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model, as
        LatentMoEConfig's, over FOUR pools of two cache kinds: the full
        layers' keys and values, ``n_pages`` pages of the ``sequence``
        kind, and the window layers', ``max_batch`` rings of
        ``ring_pages`` pages and the null page. Every program takes the
        rows' ring table behind their page table, and returns
        HYBRID_STATS. The scope must already hold ``param_shapes()``."""
        if draft_cfg is not None or quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published, "
                "without a speculative form; drop draft_cfg / quantize")
        ring_pages = self.ring_pages(page_size)
        ring = {"window": self.window, "pages_per_seq": ring_pages,
                "n_pages": max_batch * ring_pages + 1, "pools": (2, 3),
                "table": ("RingTable", "ring_table")}
        pool_specs = []
        for kind, pages in ((FULL, n_pages), (WINDOW, ring["n_pages"])):
            n = max(1, self.layers_of(kind))
            for width in (self.head_dim, self.v_head_dim):
                pool_specs.append(([n, pages, page_size,
                                    self.n_kv(kind) * width], self.dtype))
        stacks = {prefix: (prefix, scope,
                           self.layer_params(n, kind, routed))
                  for prefix, scope, kind, n, routed in self.stacks()}
        lead = stacks.pop("Lead", None)
        return build_block_programs(
            self, pool_specs=pool_specs,
            common=dict(
                params={}, lead_params=lead[2] if lead else {},
                stacks=list(stacks.values()),
                attrs=self.block_attrs(page_size),
                vocab_size=self.vocab_size, dtype=self.dtype),
            max_batch=max_batch, page_size=page_size, n_pages=n_pages,
            pages_per_seq=pages_per_seq, prompt_buckets=prompt_buckets,
            decode_block=decode_block, chunk_size=chunk_size,
            kinds={"window": ring},
            stats=HYBRID_STATS)


# unequal key/value head counts (2 | 4) and key and value widths (12 | 8),
# 4 of 12 widths rotated, a window of 4; one chip's share: a 16-wide
# router, 3 experts a token, experts 4-7 held
HYBRID_MOE_TINY = HybridMoEConfig(
    name="hybrid-moe-tiny", vocab_size=96, dim=32,
    layer_pattern=(0, 1, 1, 0, 1), n_dense_layers=1, n_heads=4,
    head_dim=12, v_head_dim=8, n_kv_full=2, n_kv_window=4,
    rope_base_full=5e4, rope_base_window=1e2, rotary_dim=4,
    value_scale=0.707, window=4, ffn_hidden=64, n_experts=4,
    router_width=16, experts_first=4, moe_top_k=3, expert_hidden=16,
    dtype="float32")
