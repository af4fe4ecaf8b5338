"""Decoder-only models that MIX TWO KINDS OF ATTENTION LAYER in one stack,
for serving: grouped-query attention whose layers are either ``full``
(every earlier position is seen) or ``window`` (the query and the
``window - 1`` positions before it, with a learned sink a head in the
softmax's denominator), each kind with its own key/value head count and
rotary base; keys wider than values, a part of each head rotated, the
values scaled; sigmoid-routed experts (ops/moe.py) on the plain residual
path behind leading dense layers. MiMo-V2-Flash's block is a value of
``HybridMoEConfig``, and Laguna-XS.2's is another: the window layers with
their OWN query-head count and rotation (``n_heads_window``,
``rotary_dim_window``; YaRN's frequencies and factor in the full layers,
``yarn_full``), a sigmoid gate on every head's result (``head_gate``), a
softmax router (``scoring``) and a shared expert beside the routed ones
(``shared_hidden``), every expert held. A configuration that names none
of these builds the parameters and the programs it built before them.

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

from ..ops.transformer_ops import HYBRID_STATS, yarn_inv_freq
from .latent_moe import build_block_programs

__all__ = ["HybridMoEConfig", "HYBRID_MOE_TINY", "HYBRID_GATED_TINY"]

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
    n_heads_window: int = None       # query heads of a window layer
    rotary_dim_window: int = None    # and the widths it rotates
    # the full layers' YaRN: factor, original_max, beta_fast, beta_slow
    # (ops/transformer_ops.py yarn_inv_freq) and attention_factor, which
    # multiplies their cosines and sines
    yarn_full: dict = None
    head_gate: bool = False          # sigmoid(u Wg) a head, before Wo
    scoring: str = "sigmoid"         # the router's (ops/moe.py moe_route)
    shared_hidden: int = 0           # a shared expert's SwiGLU; 0: none

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
        for rd in (self.rotary_dim, self.rotary(WINDOW)):
            if rd % 2 or rd > self.head_dim:
                raise ValueError(f"{self.name}: cannot rotate {rd} of "
                                 f"{self.head_dim} widths")
        for kind in (FULL, WINDOW):
            if self.heads(kind) % self.n_kv(kind):
                raise ValueError(
                    f"{self.name}: {self.heads(kind)} query heads over "
                    f"{self.n_kv(kind)} key/value heads")

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

    def heads(self, kind):
        """Query heads of a layer of attention kind ``kind``."""
        return self.n_heads_window if kind == WINDOW \
            and self.n_heads_window else self.n_heads

    def rotary(self, kind):
        """Leading widths a head of kind ``kind`` rotates."""
        return self.rotary_dim if kind == FULL \
            or self.rotary_dim_window is None else self.rotary_dim_window

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
        # a kind's own heads and rotation, named only where the model has
        # them: a configuration without them keeps its attributes
        for kind, spec in zip((FULL, WINDOW), attn_kinds):
            if self.n_heads_window:
                spec["n_heads"] = self.heads(kind)
            if self.rotary_dim_window is not None:
                spec["rotary_dim"] = self.rotary(kind)
        if self.yarn_full:
            y = self.yarn_full
            attn_kinds[FULL]["inv_freq"] = [float(f) for f in yarn_inv_freq(
                self.rotary_dim, self.rope_base_full, y["factor"],
                y["original_max"], y["beta_fast"], y["beta_slow"])]
            attn_kinds[FULL]["rope_factor"] = float(y["attention_factor"])
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "gqa", "ffn": "routed", "residual": "plain",
            "moe_top_k": self.moe_top_k, "scoring": self.scoring,
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
        ``n_experts`` held experts), its bias (a sigmoid router's) and
        the sinks are float32 whatever ``dtype`` is."""
        L, D, H, G = n_layers, self.dim, self.heads(kind), self.n_kv(kind)
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
        if self.head_gate:
            out["Wg"] = ("wg", [L, D, H], dt)
        if not routed:
            F = self.ffn_hidden
            out.update(WGate=("w_gate", [L, D, F], dt),
                       WUp=("w_up", [L, D, F], dt),
                       WDown=("w_down", [L, F, D], dt))
            return out
        E, F, R = self.n_experts, self.expert_hidden, self.router_width
        out["MoeRouter"] = ("moe_router", [L, D, R], "float32")
        if self.scoring == "sigmoid":
            out["MoeBias"] = ("moe_bias", [L, R], "float32")
        out.update(MoeWGate=("moe_w_gate", [L, E, D, F], dt),
                   MoeWUp=("moe_w_up", [L, E, D, F], dt),
                   MoeWDown=("moe_w_down", [L, E, F, D], dt))
        if self.shared_hidden:
            S = self.shared_hidden
            out.update(ShWGate=("sh_w_gate", [L, D, S], dt),
                       ShWUp=("sh_w_up", [L, D, S], dt),
                       ShWDown=("sh_w_down", [L, S, D], dt))
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

# Laguna-XS.2's mechanisms small: 6 query heads over 2 key/value heads in
# full layers (3 a group: not a sublane tile) and 8 in window layers, 4 of
# 8 widths rotated with YaRN's frequencies and factor in full layers and
# all 8 plainly in window layers, a gate on every head, a softmax router
# over 8 experts ALL held (3 a token, the routed sum scaled 2.5) beside a
# shared expert, no sinks, no value scale, a window of 4
HYBRID_GATED_TINY = HybridMoEConfig(
    name="hybrid-gated-tiny", vocab_size=96, dim=32,
    layer_pattern=(0, 1, 1, 1, 0), n_dense_layers=1, n_heads=6,
    n_heads_window=8, head_dim=8, v_head_dim=8, n_kv_full=2, n_kv_window=2,
    rope_base_full=5e2, rope_base_window=1e2, rotary_dim=4,
    rotary_dim_window=8, yarn_full=dict(
        factor=8.0, original_max=8, beta_fast=4.0, beta_slow=1.0,
        attention_factor=1.2), value_scale=1.0, window=4, sink_window=False,
    head_gate=True, scoring="softmax", shared_hidden=16, ffn_hidden=64,
    n_experts=8, moe_top_k=3, expert_hidden=16, route_scale=2.5,
    norm_eps=1e-6, dtype="float32")
