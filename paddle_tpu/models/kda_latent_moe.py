"""Decoder-only models most of whose layers are KIMI DELTA ATTENTION (the
delta rule with a decay A CHANNEL, ops/delta_rule.py: a matrix state a
head, a prompt computed in chunks as matrix products) and the rest LATENT
attention (one ``[kv_rank | rope_dim]`` entry a token, absorbed in decode),
with group-limited sigmoid-routed experts and a shared expert in every
layer behind leading dense ones, for serving. Ling-3.0-flash's block is a
value of ``KdaLatentMoEConfig``, whole or as ONE CHIP'S SHARE of an
expert-parallel layer (the router ``router_width`` wide, ``n_experts`` of
its experts held from ``experts_first`` on: models/latent_moe.py).

The block is ops/transformer_ops.py ``block_forward`` at these kinds
(``latent`` | ``kda`` x ``swiglu`` | ``routed``, ``plain``), the kind of
each layer being DATA of ``BlockKinds`` (``attn_kinds``, ``layer_kinds``):
the latent kind as models/latent_moe.py has it for a whole stack, here one
kind among two, without the query's low-rank pair and with a gate a head;
the ``kda`` kind models/hybrid_delta.py's ``delta`` with its own ``rule``
(a sigmoid write strength, the lower-bound gate) and a sigmoid output
gate. Two cache kinds in one manager: ``sequence`` pages for the latent
layers, ONE pool ``[latent layers, pages, page_size, stored entry]``, and
ONE ``state`` entry a request for the others, ``[kda layers, max_batch + 1,
heads, dk, dv]`` float32 | ``[kda layers, max_batch + 1, (d_conv - 1) * C]``,
C = heads x (2 dk + dv); two tables a row, and three stacks of layer
parameters (``lead.*`` the leading dense layers, ``latent.*``, ``kda.*``).

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill,
chunk and decode programs; there is no training graph.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import KDA_LATENT_STATS, whole_tiles
from .hybrid_moe import HybridMoEConfig
from .latent_moe import build_block_programs

__all__ = ["KdaLatentMoEConfig", "KDA_LATENT_TINY"]

LATENT, KDA = 0, 1          # a layer's kind, as ``layer_pattern`` has it


@dataclass
class KdaLatentMoEConfig:
    name: str = "kda-latent-moe"
    vocab_size: int = 157184
    dim: int = 2560
    layer_pattern: tuple = (1, 1, 1, 1, 1, 0)   # 0 latent | 1 kda
    n_dense_layers: int = 2          # leading layers with a dense SwiGLU
    n_heads: int = 32                # of both kinds of layer
    kv_rank: int = 512               # the latent layers' widths
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_base: float = 6e6
    kda_key_dim: int = 128           # dk: a state [dk, dv] a head
    kda_value_dim: int = 128         # dv
    d_conv: int = 4                  # k: the causal convolution's taps
    gate_floor: float = -5.0         # the log decay a position lies above
    ffn_hidden: int = 6144           # the leading dense layers' SwiGLU
    n_experts: int = 512             # routed experts held
    router_width: int = None         # experts routed over (None: those held)
    experts_first: int = 0           # the first expert held
    n_group: int = 8                 # group-limited selection (moe_route)
    topk_group: int = 4
    moe_top_k: int = 8
    expert_hidden: int = 768
    n_shared: int = 1
    route_scale: float = 2.5
    route_eps: float = 1e-20         # beside the picked scores' sum
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    stats = KDA_LATENT_STATS    # what its programs count on the device

    def __post_init__(self):
        self.layer_pattern = tuple(int(k) for k in self.layer_pattern)
        if self.router_width is None:
            self.router_width = self.n_experts
        lead = self.layer_pattern[:self.n_dense_layers]
        if set(self.layer_pattern) != {LATENT, KDA} or len(set(lead)) > 1 \
                or not self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"{self.name}: layer_pattern {self.layer_pattern} must "
                "hold both 0 (latent) and 1 (kda), its leading dense "
                "layers must be of one kind, and a routed layer must "
                "follow them")
        if not 0 <= self.experts_first \
                <= self.router_width - self.n_experts \
                or self.router_width % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.name}: experts {self.experts_first} to "
                f"{self.experts_first + self.n_experts - 1} of a router's "
                f"{self.router_width} in {self.n_group} groups of which "
                f"{self.topk_group} are kept")
        if self.rope_dim % 2 or self.d_conv < 2 or not self.gate_floor < 0:
            raise ValueError(
                f"{self.name}: a rotated part of {self.rope_dim}, a "
                f"convolution of {self.d_conv} taps, or a gate floor of "
                f"{self.gate_floor}")

    @property
    def n_layers(self):
        return len(self.layer_pattern)

    @property
    def layer_kinds(self):
        return self.layer_pattern

    @property
    def conv_channels(self):
        return self.n_heads * (2 * self.kda_key_dim + self.kda_value_dim)

    @property
    def stored_dim(self):
        """The width the latent pool keeps an entry at: whole lane tiles
        (512 + 64 is stored 640 wide: models/latent_moe.py)."""
        return whole_tiles(self.kv_rank + self.rope_dim)

    # what does not name a mixer is HybridMoEConfig's: (kind, routed) ->
    # layers; every stack's parameters by name
    layers_of = HybridMoEConfig.layers_of
    param_shapes = HybridMoEConfig.param_shapes

    def state_spec(self):
        """A sequence's entry in one kda layer as the pools store it:
        [(shape, dtype)], the heads' states (float32 whatever ``dtype``
        is) and the convolution's tail."""
        return [((self.n_heads, self.kda_key_dim, self.kda_value_dim),
                 "float32"),
                (((self.d_conv - 1) * self.conv_channels,), self.dtype)]

    def block_attrs(self, page_size):
        attn_kinds = [
            {"name": "latent", "mixer": "latent", "n_kv": self.n_heads,
             "base": self.rope_base, "window": None, "sink": False,
             "stack": "Latent", "pools": [0]},
            {"name": "kda", "mixer": "kda", "n_kv": self.n_heads,
             "base": 0.0, "window": None, "sink": False, "stack": "Kda",
             "pools": [1, 2],
             "rule": {"scope": "kda", "beta_max": 1.0,
                      "floor": float(self.gate_floor)}}]
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "latent", "ffn": "routed", "residual": "plain",
            "moe_top_k": self.moe_top_k, "scoring": "sigmoid",
            "route_scale": self.route_scale, "route_eps": self.route_eps,
            "n_group": self.n_group, "topk_group": self.topk_group,
            "experts_first": self.experts_first, "kv_rank": self.kv_rank,
            "rope_dim": self.rope_dim, "nope_dim": self.nope_dim,
            "v_dim": self.v_dim,
            "rope_inv_freq": [
                float(self.rope_base ** (-2.0 * i / self.rope_dim))
                for i in range(self.rope_dim // 2)],
            "softmax_scale": (self.nope_dim + self.rope_dim) ** -0.5,
            "n_streams": 1, "sinkhorn_iters": 0, "hc_eps": 1e-6,
            "hc_clamp": [-30.0, 30.0], "attn_kinds": attn_kinds,
            "layer_kinds": list(self.layer_pattern),
            "page_size": int(page_size)}

    def layer_params(self, n_layers, kind, routed):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        of kind ``kind`` with a routed (else dense) feed-forward. The
        decay's ``A_log`` [heads] and bias [heads * dk], the router (as
        wide as ``router_width``, beside ``n_experts`` held experts) and
        its selection bias are float32 whatever ``dtype`` is."""
        L, D, H, dt = n_layers, self.dim, self.n_heads, self.dtype
        out = {"AttnNorm": ("attn_norm", [L, D], dt),
               "MlpNorm": ("mlp_norm", [L, D], dt)}
        if kind == LATENT:
            out.update(
                Wq=("wq", [L, D, H * (self.nope_dim + self.rope_dim)], dt),
                Wkva=("wkva", [L, D, self.kv_rank + self.rope_dim], dt),
                KvNorm=("kv_norm", [L, self.kv_rank], dt),
                Wkvb=("wkvb", [L, self.kv_rank,
                               H * (self.nope_dim + self.v_dim)], dt),
                Wg=("wg", [L, D, H], dt),
                Wo=("wo", [L, H * self.v_dim, D], dt))
        else:
            dk, dv = self.kda_key_dim, self.kda_value_dim
            out.update(
                Wq=("wq", [L, D, H * dk], dt),
                Wk=("wk", [L, D, H * dk], dt),
                Wv=("wv", [L, D, H * dv], dt),
                Wz=("wz", [L, D, H * dv], dt),
                Wa=("wa", [L, D, H * dk], dt), Wb=("wb", [L, D, H], dt),
                ConvW=("conv_w", [L, self.d_conv, self.conv_channels], dt),
                ALog=("a_log", [L, H], "float32"),
                DtBias=("dt_bias", [L, H * dk], "float32"),
                GNorm=("g_norm", [L, dv], dt),
                Wo=("wo", [L, H * dv, D], dt))
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

    def stacks(self):
        """(slot prefix, scope name, kind, layers, routed) of every
        non-empty stack of layer parameters: the leading dense layers,
        then the routed layers of each kind."""
        out = [("Lead", "lead", self.layer_pattern[0],
                self.n_dense_layers, False)] if self.n_dense_layers else []
        out += [(prefix, scope, kind, self.layers_of(kind, True), True)
                for prefix, scope, kind in (("Latent", "latent", LATENT),
                                            ("Kda", "kda", KDA))]
        return [s for s in out if s[3]]

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model, as
        HybridConvMoEConfig's, over THREE pools of two cache kinds: the
        latent layers' entries, ``n_pages`` pages of the ``sequence``
        kind, and the kda layers' states and tails, ``max_batch`` entries
        of the ``state`` kind and the null entry. Every program takes the
        rows' state table behind their page table, and returns ``stats``.
        The scope must already hold ``param_shapes()``."""
        if draft_cfg is not None or quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published, "
                "without a speculative form; drop draft_cfg / quantize")
        state = {"pages_per_seq": 1, "n_pages": max_batch + 1,
                 "pools": (1, 2), "unit": "entries",
                 "table": ("StateTable", "state_table")}
        pool_specs = [([self.layers_of(LATENT), n_pages, page_size,
                        self.stored_dim], self.dtype)] + [
            ([self.layers_of(KDA), state["n_pages"]] + list(shape), dt)
            for shape, dt in self.state_spec()]
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
            stats=self.stats, kinds={"state": state})


# Ling-3.0-flash's mechanisms small, one chip's share of four: a leading
# dense kda layer, then kda, kda, latent, kda, kda; 2 heads, states of 4 x
# 6, a latent of 16 + 8 stored 128 wide, 4 taps, a 16-wide router in 4
# groups of which 2 are kept, 3 experts a token, experts 4-7 held
KDA_LATENT_TINY = KdaLatentMoEConfig(
    name="kda-latent-tiny", vocab_size=96, dim=32,
    layer_pattern=(1, 1, 1, 0, 1, 1), n_dense_layers=1, n_heads=2,
    kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8, rope_base=1e4,
    kda_key_dim=4, kda_value_dim=6, d_conv=4, gate_floor=-5.0,
    ffn_hidden=64, n_experts=4, router_width=16, experts_first=4,
    n_group=4, topk_group=2, moe_top_k=3, expert_hidden=16, n_shared=1,
    dtype="float32")
