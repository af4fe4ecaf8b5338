"""Decoder-only models whose ONE stack of layers is run several times a
token (looped language models, arXiv:2510.25741), for serving. Ouro-2.6B
is a value of ``LoopedConfig``: 48 dense layers run ``passes`` = 4 times,
the same weights in every pass, the model's final norm after EVERY pass
and its output carried into the next; the head reads the last.

The block is ops/transformer_ops.py ``block_forward`` at ``gqa`` +
``swiglu`` + ``plain`` with a norm on each side of both sublayers (the
``AttnPostNorm`` / ``MlpPostNorm`` parameters: ``x + norm(f(norm(x)))``),
and the traversal is ``BlockKinds.passes``: both are data the programs
read, no model's name. What it asks of the serving path is a cache that
is DEEPER THAN THE WEIGHTS: every pass attends keys and values of its
own, so a position leaves ``passes x n_layers`` entries and the pools are
``[passes * n_layers, pages, page_size, n_kv, head_dim]``, layer ``j`` of
pass ``s`` at ``s * n_layers + j``. At Ouro-2.6B's sizes that is 192
layer-caches, 1.5 MB a position: the pool, not the slots, bounds the
batch, and no program holds a view of it: the decode steps attend the
pages where they lie (``_PagedRunner.decode_step``).

The exit gate (``exit_gate.w`` [dim], ``exit_gate.b`` [1]) is computed on
every pass's normed output and moves no logit at the published exit
threshold of 1 (``_PagedRunner._exit_gate``). Not built: an exit below
that threshold (a number of passes a token), caches shared between passes.

Serving only: ``build_paged_programs`` gives DecodeEngine the prefill and
decode programs through ``latent_moe.build_block_programs``; there is no
training graph.
"""
from dataclasses import dataclass

from ..ops.transformer_ops import LOOP_STATS, PAGED_STATS
from .latent_moe import build_block_programs

__all__ = ["LoopedConfig", "LOOPED_TINY"]


@dataclass
class LoopedConfig:
    name: str = "looped"
    vocab_size: int = 49152
    dim: int = 2048
    n_layers: int = 48
    passes: int = 4                  # times the stack is run a token
    n_heads: int = 16
    n_kv: int = 16
    head_dim: int = 128
    ffn_hidden: int = 5632
    post_norm: bool = True           # a norm behind each sublayer too
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.passes < 1 or self.n_heads % self.n_kv:
            raise ValueError(f"{self.name}: {self.passes} passes, or "
                             f"{self.n_heads} query heads over {self.n_kv}")

    @property
    def cache_layers(self):
        """Layers of the cache: a layer of the weights has one a pass."""
        return self.passes * self.n_layers

    def cache_spec(self):
        """A token's cache entries in ONE of the ``cache_layers`` as the
        pools store them: [(shape, dtype)], keys and values."""
        return [((self.n_kv, self.head_dim), self.dtype)] * 2

    def block_attrs(self, page_size):
        return {
            "n_heads": self.n_heads, "n_kv": self.n_kv,
            "rope_base": self.rope_base, "epsilon": self.norm_eps,
            "attention": "gqa", "ffn": "swiglu", "residual": "plain",
            "passes": self.passes,
            "moe_top_k": 1, "scoring": "sigmoid", "route_scale": 1.0,
            "n_group": 1, "topk_group": 1, "experts_first": 0,
            "kv_rank": 0, "rope_dim": 0, "nope_dim": 0,
            "v_dim": self.head_dim, "rope_inv_freq": [],
            "softmax_scale": None, "n_streams": 1, "sinkhorn_iters": 0,
            "hc_eps": 1e-6, "hc_clamp": [-30.0, 30.0],
            "key_dim": self.head_dim, "page_size": int(page_size)}

    def layer_params(self):
        """slot -> (suffix, shape, dtype) of the stacked layers."""
        L, D, F, dt = self.n_layers, self.dim, self.ffn_hidden, self.dtype
        H, G, hd = self.n_heads, self.n_kv, self.head_dim
        out = {"AttnNorm": ("attn_norm", [L, D], dt),
               "MlpNorm": ("mlp_norm", [L, D], dt)}
        if self.post_norm:
            out.update(AttnPostNorm=("attn_post_norm", [L, D], dt),
                       MlpPostNorm=("mlp_post_norm", [L, D], dt))
        out.update(Wq=("wq", [L, D, H * hd], dt),
                   Wk=("wk", [L, D, G * hd], dt),
                   Wv=("wv", [L, D, G * hd], dt),
                   Wo=("wo", [L, H * hd, D], dt),
                   WGate=("w_gate", [L, D, F], dt),
                   WUp=("w_up", [L, D, F], dt),
                   WDown=("w_down", [L, F, D], dt))
        return out

    def gate_params(self):
        """slot -> (suffix, shape, dtype) of the exit gate, Linear(dim, 1)."""
        return {"W": ("w", [self.dim], self.dtype),
                "B": ("b", [1], self.dtype)}

    def param_shapes(self):
        """Every parameter the programs read from the scope: name ->
        (shape, dtype)."""
        out = {"tok_emb": ([self.vocab_size, self.dim], self.dtype),
               "final_norm": ([self.dim], self.dtype),
               "lm_head": ([self.dim, self.vocab_size], self.dtype)}
        for scope, table in (("blocks", self.layer_params()),
                             ("exit_gate", self.gate_params())):
            for suffix, shape, dt in table.values():
                out[f"{scope}.{suffix}"] = (shape, dt)
        return out

    def build_paged_programs(self, *, max_batch, page_size, n_pages,
                             pages_per_seq, prompt_buckets,
                             decode_block=1, quantize=False,
                             draft_cfg=None, gamma=4, chunk_size=None):
        """The paged step programs DecodeEngine runs for this model, as
        LatentMoEConfig's, over TWO pools ``[cache_layers, n_pages,
        page_size, n_kv, head_dim]``, keys and values, ``passes`` times as
        deep as the weights; every program returns LOOP_STATS (a stack
        run once: PAGED_STATS). The scope
        must already hold ``param_shapes()``."""
        if draft_cfg is not None or quantize:
            raise NotImplementedError(
                f"{self.name}: served in {self.dtype} as published, "
                "without a speculative form; drop draft_cfg / quantize")
        pool_specs = [
            ([self.cache_layers, n_pages, page_size] + list(entry), dtype)
            for entry, dtype in self.cache_spec()]
        return build_block_programs(
            self, pool_specs=pool_specs,
            common=dict(
                params=self.layer_params(), lead_params={},
                stacks=[("Exit", "exit_gate", self.gate_params())],
                attrs=self.block_attrs(page_size),
                vocab_size=self.vocab_size, dtype=self.dtype),
            max_batch=max_batch, page_size=page_size, n_pages=n_pages,
            pages_per_seq=pages_per_seq, prompt_buckets=prompt_buckets,
            decode_block=decode_block, chunk_size=chunk_size,
            stats=LOOP_STATS if self.passes > 1 else PAGED_STATS)


# 2 layers run 3 times over 4 heads, each its own key/value head as the
# published model's are: no count equals another (6 cache layers), and no
# width is a lane tile's
LOOPED_TINY = LoopedConfig(
    name="looped-tiny", vocab_size=96, dim=32, n_layers=2, passes=3,
    n_heads=4, n_kv=4, head_dim=8, ffn_hidden=48, rope_base=1e4,
    dtype="float32")
