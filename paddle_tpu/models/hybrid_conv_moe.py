"""Decoder-only models most of whose layers are GATED SHORT CONVOLUTIONS
(ops/short_conv.py: a causal depthwise convolution of a few taps between
two gates, whose whole cache is its last ``d_conv - 1`` inputs a sequence)
and the rest grouped-query attention with a norm A HEAD on queries and
keys, with sigmoid-routed experts (ops/moe.py) in every layer behind
leading dense ones, for serving. LFM2-24B-A2B's block is a value of
``HybridConvMoEConfig``.

The block is ops/transformer_ops.py ``block_forward`` at these kinds
(``gqa`` | ``conv`` x ``swiglu`` | ``routed``, ``plain``), the kind of each
layer being DATA of ``BlockKinds`` (``attn_kinds``, ``layer_kinds``), as
models/hybrid_ssm.py has it for state-space layers and models/
hybrid_moe.py for routed experts behind leading dense layers. The cache
kinds are hybrid_ssm's, ``sequence`` pages for the attention layers and
ONE ``state`` entry a request for the others, but the state kind has ONE
pool: the mixer keeps a tail and no state. Three pools, ``[attention
layers, pages, page_size, n_kv * head_dim]`` keys and values and ``[conv
layers, max_batch + 1, (d_conv - 1) * dim]``, two tables a row, and three
stacks of layer parameters (``lead.*`` the leading dense layers, ``full.*``,
``conv.*``).

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill,
chunk and decode programs; there is no training graph.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import CONV_STATS
from .hybrid_moe import HybridMoEConfig
from .latent_moe import build_block_programs

__all__ = ["HybridConvMoEConfig", "HYBRID_CONV_TINY"]

FULL, CONV = 0, 1           # a layer's kind, as ``layer_pattern`` has it


@dataclass
class HybridConvMoEConfig:
    name: str = "hybrid-conv-moe"
    vocab_size: int = 65536
    dim: int = 2048
    layer_pattern: tuple = (1, 1, 0, 1, 1, 1)   # 0 attention | 1 conv
    n_dense_layers: int = 2          # leading layers with a dense SwiGLU
    n_heads: int = 32
    n_kv: int = 8
    head_dim: int = 64
    rope_base: float = 1e6
    d_conv: int = 3                  # k: the convolution's taps
    ffn_hidden: int = 11776          # the leading dense layers' SwiGLU
    n_experts: int = 64              # routed experts, all held
    moe_top_k: int = 4
    expert_hidden: int = 1536
    route_scale: float = 1.0
    route_eps: float = 1e-6          # beside the picked scores' sum
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    stats = CONV_STATS      # what its programs count on the device

    def __post_init__(self):
        self.layer_pattern = tuple(int(k) for k in self.layer_pattern)
        lead = self.layer_pattern[:self.n_dense_layers]
        if set(self.layer_pattern) != {FULL, CONV} or len(set(lead)) > 1 \
                or not self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"{self.name}: layer_pattern {self.layer_pattern} must "
                "hold both 0 (attention) and 1 (conv), its leading dense "
                "layers must be of one kind, and a routed layer must "
                "follow them")
        if self.n_heads % self.n_kv or self.head_dim % 2 \
                or self.d_conv < 2:
            raise ValueError(
                f"{self.name}: {self.n_heads} query heads over "
                f"{self.n_kv}, heads of {self.head_dim}, or a convolution "
                f"of {self.d_conv} taps")

    @property
    def n_layers(self):
        return len(self.layer_pattern)

    @property
    def layer_kinds(self):
        return self.layer_pattern

    # what does not name a mixer is HybridMoEConfig's: (kind, routed) ->
    # layers; every stack's parameters by name (``lm_head`` an array of
    # its own, [dim, vocab]: tied, it holds the embedding's transpose)
    layers_of = HybridMoEConfig.layers_of
    param_shapes = HybridMoEConfig.param_shapes

    def state_spec(self):
        """A sequence's entry in one conv layer as the pool stores it:
        [(shape, dtype)], the convolution's tail and nothing else."""
        return [(((self.d_conv - 1) * self.dim,), self.dtype)]

    def block_attrs(self, page_size):
        attn_kinds = [
            {"name": "full", "n_kv": self.n_kv, "base": self.rope_base,
             "window": None, "sink": False, "stack": "Full",
             "pools": [0, 1]},
            {"name": "conv", "mixer": "conv", "n_kv": self.n_kv,
             "base": 0.0, "window": None, "sink": False, "stack": "Conv",
             "pools": [2]}]
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "gqa", "ffn": "routed", "residual": "plain",
            "moe_top_k": self.moe_top_k, "scoring": "sigmoid",
            "route_scale": self.route_scale, "route_eps": self.route_eps,
            "n_group": 1, "topk_group": 1, "experts_first": 0,
            "kv_rank": 0, "rope_dim": 0, "nope_dim": 0,
            "v_dim": self.head_dim, "rope_inv_freq": [],
            "softmax_scale": None, "n_streams": 1, "sinkhorn_iters": 0,
            "hc_eps": 1e-6, "hc_clamp": [-30.0, 30.0],
            "key_dim": self.head_dim, "rotary_dim": self.head_dim,
            "value_scale": 1.0, "attn_kinds": attn_kinds,
            "layer_kinds": list(self.layer_pattern),
            "page_size": int(page_size)}

    def layer_params(self, n_layers, kind, routed):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        of kind ``kind`` with a routed (else dense) feed-forward. The
        query/key norms are ONE weight a head wide each; the router and
        its selection bias are float32 whatever ``dtype`` is."""
        L, D, dt = n_layers, self.dim, self.dtype
        out = {"AttnNorm": ("attn_norm", [L, D], dt),
               "MlpNorm": ("mlp_norm", [L, D], dt)}
        if kind == FULL:
            H, G, hd = self.n_heads, self.n_kv, self.head_dim
            out.update(Wq=("wq", [L, D, H * hd], dt),
                       Wk=("wk", [L, D, G * hd], dt),
                       Wv=("wv", [L, D, G * hd], dt),
                       QNorm=("q_norm", [L, hd], dt),
                       KNorm=("k_norm", [L, hd], dt),
                       Wo=("wo", [L, H * hd, D], dt))
        else:
            out.update(WIn=("w_in", [L, D, 3 * D], dt),
                       ConvW=("conv_w", [L, self.d_conv, D], dt),
                       WOut=("w_out", [L, D, D], dt))
        if not routed:
            F = self.ffn_hidden
            out.update(WGate=("w_gate", [L, D, F], dt),
                       WUp=("w_up", [L, D, F], dt),
                       WDown=("w_down", [L, F, D], dt))
            return out
        E, F = self.n_experts, self.expert_hidden
        out.update(MoeRouter=("moe_router", [L, D, E], "float32"),
                   MoeBias=("moe_bias", [L, E], "float32"),
                   MoeWGate=("moe_w_gate", [L, E, D, F], dt),
                   MoeWUp=("moe_w_up", [L, E, D, F], dt),
                   MoeWDown=("moe_w_down", [L, E, F, D], dt))
        return out

    def stacks(self):
        """(slot prefix, scope name, kind, layers, routed) of every
        non-empty stack of layer parameters: the leading dense layers,
        then the routed layers of each kind."""
        out = [("Lead", "lead", self.layer_pattern[0],
                self.n_dense_layers, False)] if self.n_dense_layers else []
        out += [(prefix, scope, kind, self.layers_of(kind, True), True)
                for prefix, scope, kind in (("Full", "full", FULL),
                                            ("Conv", "conv", CONV))]
        return [s for s in out if s[3]]

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model, as
        HybridSSMConfig's, over THREE pools of two cache kinds: the
        attention layers' keys and values, ``n_pages`` pages of the
        ``sequence`` kind, and the conv layers' tails, ``max_batch``
        entries of the ``state`` kind and the null entry. Every program
        takes the rows' state table behind their page table, and returns
        ``stats``. The scope must already hold ``param_shapes()``."""
        if draft_cfg is not None or quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published, "
                "without a speculative form; drop draft_cfg / quantize")
        state = {"pages_per_seq": 1, "n_pages": max_batch + 1,
                 "pools": (2,), "unit": "entries",
                 "table": ("StateTable", "state_table")}
        kv = [self.layers_of(FULL), n_pages, page_size,
              self.n_kv * self.head_dim]
        pool_specs = [(kv, self.dtype), (kv, self.dtype)] + [
            ([self.layers_of(CONV), state["n_pages"]] + list(shape), dt)
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


# LFM2-24B-A2B's mechanisms small: a leading dense conv layer, then
# attention, conv, conv, attention, conv; 4 query heads over 2 key/value
# heads of 8 with a norm a head, 3 taps, 8 experts of which 2 a token
HYBRID_CONV_TINY = HybridConvMoEConfig(
    name="hybrid-conv-tiny", vocab_size=96, dim=32,
    layer_pattern=(1, 0, 1, 1, 0, 1), n_dense_layers=1, n_heads=4, n_kv=2,
    head_dim=8, rope_base=1e4, d_conv=3, ffn_hidden=64, n_experts=8,
    moe_top_k=2, expert_hidden=16, dtype="float32")
