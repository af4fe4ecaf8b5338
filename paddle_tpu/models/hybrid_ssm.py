"""Decoder-only models most of whose layers are SELECTIVE STATE-SPACE
MIXERS (Mamba-1 with the Jamba family's inner norms: ops/ssm.py) and the
rest grouped-query attention without any position embedding, a dense
SwiGLU in every layer, for serving. AI21-Jamba2-3B's block is a value of
``HybridSSMConfig``: layer ``i`` is attention where ``i % attn_period ==
attn_offset``.

The block is ops/transformer_ops.py ``block_forward`` at these kinds
(``gqa`` | ``ssm`` + ``swiglu`` + ``plain``), the kind of each layer being
DATA of ``BlockKinds`` (``attn_kinds``, ``layer_kinds``), as
models/hybrid_moe.py has it for full and window attention. What it asks
of the serving path is a cache kind that is NOT INDEXED BY POSITION: an
attention layer's entries live as long as the sequence, a page for every
``page_size`` positions (``sequence``); a state-space layer keeps ONE
entry a sequence whatever its length (``state``): the recurrent state
``[d_state, d_inner]`` float32 and the last ``d_conv - 1`` inputs of its
convolution. So the model has four pools, ``[attention layers, pages,
page_size, n_kv * head_dim]`` keys and values and ``[state layers,
max_batch + 1, d_state, d_inner]`` | ``[state layers, max_batch + 1,
(d_conv - 1) * d_inner]``, two tables a row, and two stacks of layer
parameters (``full.*``, ``ssm.*``).

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill,
chunk and decode programs; there is no training graph.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import SSM_STATS
from .latent_moe import build_block_programs

__all__ = ["HybridSSMConfig", "HYBRID_SSM_TINY"]

FULL, SSM = 0, 1            # a layer's kind, as ``layer_kinds`` has it


@dataclass
class HybridSSMConfig:
    name: str = "hybrid-ssm"
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    attn_period: int = 14            # layer i is attention where
    attn_offset: int = 7             # i % attn_period == attn_offset
    n_heads: int = 20
    n_kv: int = 1
    head_dim: int = 128
    ffn_hidden: int = 8192
    d_state: int = 16                # N: states a channel
    d_conv: int = 4                  # k: the causal convolution's taps
    dt_rank: int = 160               # R
    expand: int = 2                  # d_inner = expand * dim
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    stats = SSM_STATS       # what its programs count on the device

    def __post_init__(self):
        kinds = set(self.layer_kinds)
        if kinds != {FULL, SSM}:
            raise ValueError(
                f"{self.name}: {self.n_layers} layers with attention at "
                f"i % {self.attn_period} == {self.attn_offset} hold not "
                "both kinds of layer")
        if self.n_heads % self.n_kv or self.d_conv < 2:
            raise ValueError(f"{self.name}: {self.n_heads} query heads "
                             f"over {self.n_kv}, or a convolution of "
                             f"{self.d_conv} taps")

    @property
    def d_inner(self):
        return self.expand * self.dim

    @property
    def layer_kinds(self):
        return tuple(FULL if i % self.attn_period == self.attn_offset
                     else SSM for i in range(self.n_layers))

    def layers_of(self, kind):
        return self.layer_kinds.count(kind)

    def state_spec(self):
        """A sequence's entry in one state layer as the pools store it:
        [(shape, dtype)], the recurrent state and the convolution's tail."""
        return [((self.d_state, self.d_inner), "float32"),
                (((self.d_conv - 1) * self.d_inner,), self.dtype)]

    def block_attrs(self, page_size):
        attn_kinds = [
            {"name": "full", "n_kv": self.n_kv, "base": 0.0,
             "window": None, "sink": False, "stack": "Full",
             "pools": [0, 1]},
            {"name": "state", "mixer": "ssm", "n_kv": self.n_kv,
             "base": 0.0, "window": None, "sink": False, "stack": "Ssm",
             "pools": [2, 3]}]
        return {
            "n_heads": self.n_heads, "epsilon": self.norm_eps,
            "attention": "gqa", "ffn": "swiglu", "residual": "plain",
            "moe_top_k": 1, "scoring": "sigmoid", "route_scale": 1.0,
            "n_group": 1, "topk_group": 1, "experts_first": 0,
            "kv_rank": 0, "rope_dim": 0, "nope_dim": 0,
            "v_dim": self.head_dim, "rope_inv_freq": [],
            "softmax_scale": None, "n_streams": 1, "sinkhorn_iters": 0,
            "hc_eps": 1e-6, "hc_clamp": [-30.0, 30.0],
            "key_dim": self.head_dim, "rotary_dim": 0, "value_scale": 1.0,
            "attn_kinds": attn_kinds,
            "layer_kinds": list(self.layer_kinds),
            "page_size": int(page_size)}

    def layer_params(self, n_layers, kind):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        of ``kind``. The step's bias, ``A_log`` (stored [d_state, d_inner],
        as the state lies) and ``D`` are float32 whatever ``dtype`` is."""
        L, D, F, dt = n_layers, self.dim, self.ffn_hidden, self.dtype
        out = {"AttnNorm": ("attn_norm", [L, D], dt),
               "MlpNorm": ("mlp_norm", [L, D], dt)}
        if kind == FULL:
            H, G, hd = self.n_heads, self.n_kv, self.head_dim
            out.update(Wq=("wq", [L, D, H * hd], dt),
                       Wk=("wk", [L, D, G * hd], dt),
                       Wv=("wv", [L, D, G * hd], dt),
                       Wo=("wo", [L, H * hd, D], dt))
        else:
            C, N, R = self.d_inner, self.d_state, self.dt_rank
            out.update(
                WIn=("w_in", [L, D, 2 * C], dt),
                ConvW=("conv_w", [L, self.d_conv, C], dt),
                ConvB=("conv_b", [L, C], dt),
                WX=("w_x", [L, C, R + 2 * N], dt),
                DtNorm=("dt_norm", [L, R], dt),
                BNorm=("b_norm", [L, N], dt),
                CNorm=("c_norm", [L, N], dt),
                WDt=("w_dt", [L, R, C], dt),
                DtBias=("dt_bias", [L, C], "float32"),
                ALog=("a_log", [L, N, C], "float32"),
                D=("d", [L, C], "float32"),
                WOut=("w_out", [L, C, D], dt))
        out.update(WGate=("w_gate", [L, D, F], dt),
                   WUp=("w_up", [L, D, F], dt),
                   WDown=("w_down", [L, F, D], dt))
        return out

    def stacks(self):
        """(slot prefix, scope name, kind, layers) of each kind's stack."""
        return [("Full", "full", FULL, self.layers_of(FULL)),
                ("Ssm", "ssm", SSM, self.layers_of(SSM))]

    def param_shapes(self):
        """Every parameter the programs read from the scope: name ->
        (shape, dtype). ``lm_head`` is an array of its own, [dim, vocab]:
        a model with tied embeddings holds the embedding's transpose
        there."""
        out = {"tok_emb": ([self.vocab_size, self.dim], self.dtype),
               "final_norm": ([self.dim], self.dtype),
               "lm_head": ([self.dim, self.vocab_size], self.dtype)}
        for _, scope, kind, n in self.stacks():
            for suffix, shape, dt in self.layer_params(n, kind).values():
                out[f"{scope}.{suffix}"] = (shape, dt)
        return out

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model, as
        HybridMoEConfig's, over FOUR pools of two cache kinds: the
        attention layers' keys and values, ``n_pages`` pages of the
        ``sequence`` kind, and the state layers' states and tails,
        ``max_batch`` entries of the ``state`` kind and the null entry.
        Every program takes the rows' state table behind their page
        table, and returns ``stats``. The scope must already hold
        ``param_shapes()``."""
        if draft_cfg is not None or quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published, "
                "without a speculative form; drop draft_cfg / quantize")
        state = {"pages_per_seq": 1, "n_pages": max_batch + 1,
                 "pools": (2, 3), "unit": "entries",
                 "table": ("StateTable", "state_table")}
        kv = [self.layers_of(FULL), n_pages, page_size,
              self.n_kv * self.head_dim]
        pool_specs = [(kv, self.dtype), (kv, self.dtype)] + [
            ([self.layers_of(SSM), state["n_pages"]] + list(shape), dt)
            for shape, dt in self.state_spec()]
        return build_block_programs(
            self, pool_specs=pool_specs,
            common=dict(
                params={}, lead_params={},
                stacks=[(prefix, scope, self.layer_params(n, kind))
                        for prefix, scope, kind, n in self.stacks()],
                attrs=self.block_attrs(page_size),
                vocab_size=self.vocab_size, dtype=self.dtype),
            max_batch=max_batch, page_size=page_size, n_pages=n_pages,
            pages_per_seq=pages_per_seq, prompt_buckets=prompt_buckets,
            decode_block=decode_block, chunk_size=chunk_size,
            stats=self.stats, kinds={"state": state})


# both kinds over two periods (attention at layers 1 and 4), 4 query heads
# over 1 key/value head, d_inner 48: no multiple of a lane tile
HYBRID_SSM_TINY = HybridSSMConfig(
    name="hybrid-ssm-tiny", vocab_size=96, dim=24, n_layers=6,
    attn_period=3, attn_offset=1, n_heads=4, n_kv=1, head_dim=6,
    ffn_hidden=48, d_state=4, d_conv=4, dt_rank=6, expand=2,
    dtype="float32")
