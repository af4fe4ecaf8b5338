"""Decoder-only models most of whose layers are GATED DELTA-RULE linear
attention (ops/delta_rule.py: a matrix state a head, a prompt computed in
chunks as matrix products) and the rest full attention without any
position embedding, with the family's reordered norm (``x + norm(f(x))``)
and a dense SwiGLU in every layer, for serving. Olmo-Hybrid-7B's block is
a value of ``HybridDeltaConfig``: layer ``i`` is full attention where ``i %
attn_period == attn_period - 1``.

The block is ops/transformer_ops.py ``block_forward`` at these kinds
(``gqa`` | ``delta`` + ``swiglu`` + ``plain``), as models/hybrid_ssm.py's
at its own: the kind of each layer is DATA of ``BlockKinds``, the residual
form and the query/key norm are read off the PARAMETERS (a layer holds
``AttnPostNorm`` and no ``AttnNorm``; the attention layers hold ``QNorm`` /
``KNorm``). The cache kinds are that model's too, ``sequence`` pages for
the attention layers and ONE ``state`` entry a request for the others:
four pools, ``[attention layers, pages, page_size, n_kv * head_dim]`` keys
and values and ``[delta layers, max_batch + 1, heads, dk, dv]`` float32 |
``[delta layers, max_batch + 1, (d_conv - 1) * C]``, C = heads x (2 dk +
dv) the convolved channels. What the two models share is shared: the
methods below that do not name a mixer are HybridSSMConfig's.

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill,
chunk and decode programs; there is no training graph.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import DELTA_STATS
from .hybrid_ssm import HybridSSMConfig

__all__ = ["HybridDeltaConfig", "HYBRID_DELTA_TINY"]

FULL, DELTA = 0, 1          # a layer's kind, as ``layer_kinds`` has it


@dataclass
class HybridDeltaConfig:
    name: str = "hybrid-delta"
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    attn_period: int = 4             # layer i is full attention where
                                     # i % attn_period == attn_period - 1
    n_heads: int = 30
    n_kv: int = 30
    head_dim: int = 128
    ffn_hidden: int = 11008
    delta_heads: int = 30            # H: a state [dk, dv] each
    delta_key_dim: int = 96          # dk
    delta_value_dim: int = 192       # dv
    d_conv: int = 4                  # k: the causal convolution's taps
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    stats = DELTA_STATS     # what its programs count on the device

    def __post_init__(self):
        if set(self.layer_kinds) != {FULL, DELTA}:
            raise ValueError(
                f"{self.name}: {self.n_layers} layers with full attention "
                f"every {self.attn_period} hold not both kinds of layer")
        if self.n_heads % self.n_kv or self.d_conv < 2:
            raise ValueError(f"{self.name}: {self.n_heads} query heads "
                             f"over {self.n_kv}, or a convolution of "
                             f"{self.d_conv} taps")

    @property
    def conv_channels(self):
        return self.delta_heads * (2 * self.delta_key_dim
                                   + self.delta_value_dim)

    @property
    def layer_kinds(self):
        return tuple(FULL if i % self.attn_period == self.attn_period - 1
                     else DELTA for i in range(self.n_layers))

    layers_of = HybridSSMConfig.layers_of
    param_shapes = HybridSSMConfig.param_shapes
    build_paged_programs = HybridSSMConfig.build_paged_programs

    def state_spec(self):
        """A sequence's entry in one delta layer as the pools store it:
        [(shape, dtype)], the heads' states and the convolution's tail."""
        return [((self.delta_heads, self.delta_key_dim,
                  self.delta_value_dim), "float32"),
                (((self.d_conv - 1) * self.conv_channels,), self.dtype)]

    def block_attrs(self, page_size):
        """HybridSSMConfig's, the state kind's mixer and stack this
        model's."""
        attrs = HybridSSMConfig.block_attrs(self, page_size)
        attrs["attn_kinds"][1].update(mixer="delta", stack="Delta")
        return attrs

    def layer_params(self, n_layers, kind):
        """slot -> (suffix, shape, dtype) of ``n_layers`` stacked layers
        of ``kind``. No layer holds a norm BEFORE a sublayer; the decay's
        ``A_log`` and bias are float32 whatever ``dtype`` is."""
        L, D, F, dt = n_layers, self.dim, self.ffn_hidden, self.dtype
        out = {"AttnPostNorm": ("attn_post_norm", [L, D], dt),
               "MlpPostNorm": ("mlp_post_norm", [L, D], dt)}
        if kind == FULL:
            H, G, hd = self.n_heads, self.n_kv, self.head_dim
            out.update(Wq=("wq", [L, D, H * hd], dt),
                       Wk=("wk", [L, D, G * hd], dt),
                       Wv=("wv", [L, D, G * hd], dt),
                       QNorm=("q_norm", [L, H * hd], dt),
                       KNorm=("k_norm", [L, G * hd], dt),
                       Wo=("wo", [L, H * hd, D], dt))
        else:
            H, dk, dv = (self.delta_heads, self.delta_key_dim,
                         self.delta_value_dim)
            out.update(
                Wq=("wq", [L, D, H * dk], dt),
                Wk=("wk", [L, D, H * dk], dt),
                Wv=("wv", [L, D, H * dv], dt),
                Wz=("wz", [L, D, H * dv], dt),
                Wa=("wa", [L, D, H], dt), Wb=("wb", [L, D, H], dt),
                ConvW=("conv_w", [L, self.d_conv, self.conv_channels], dt),
                ALog=("a_log", [L, H], "float32"),
                DtBias=("dt_bias", [L, H], "float32"),
                GNorm=("g_norm", [L, dv], dt),
                Wo=("wo", [L, H * dv, D], dt))
        out.update(WGate=("w_gate", [L, D, F], dt),
                   WUp=("w_up", [L, D, F], dt),
                   WDown=("w_down", [L, F, D], dt))
        return out

    def stacks(self):
        """(slot prefix, scope name, kind, layers) of each kind's stack."""
        return [("Full", "full", FULL, self.layers_of(FULL)),
                ("Delta", "delta", DELTA, self.layers_of(DELTA))]


# both kinds over two periods (full attention at layers 2 and 5), 4 heads
# of 6, 3 delta heads with states of 4 x 10: no multiple of a lane tile
HYBRID_DELTA_TINY = HybridDeltaConfig(
    name="hybrid-delta-tiny", vocab_size=96, dim=24, n_layers=6,
    attn_period=3, n_heads=4, n_kv=4, head_dim=6, ffn_hidden=48,
    delta_heads=3, delta_key_dim=4, delta_value_dim=10, d_conv=4,
    dtype="float32")
