"""The Llama-family decoder (Mistral-7B-v0.3's block) in plain float32.

Pre-norm residual blocks: RMSNorm, grouped-query attention with rotary
position embedding over pairs (x[..., :d/2], x[..., d/2:]) as the repo's
rope op and the published models have it, causal softmax, SwiGLU, then a
final RMSNorm and an untied head. No sliding window: v0.3's config has
none. Weights are taken by the names the per-layer training graph
(models/llama.py build_llama) gives them. Everything is float32 and every
product is taken at "highest" precision.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, base):
    """x: [batch, seq, heads, head_dim]; rotates (first half, second half)
    pairs by position x base**(-2i/d)."""
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(weights, tokens, *, n_layers, n_heads, n_kv_heads, rope_base,
            norm_eps):
    """Logits [batch, seq, vocab] for int tokens [batch, seq]."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        h = w["tok_emb"][tokens]
        B, T, D = h.shape
        hd = D // n_heads
        mask = jnp.tril(jnp.ones((T, T), bool))
        for i in range(n_layers):
            x = rms_norm(h, w[f"l{i}.attn_norm"], norm_eps)
            q = rope((x @ w[f"l{i}.wq"]).reshape(B, T, n_heads, hd),
                     rope_base)
            k = rope((x @ w[f"l{i}.wk"]).reshape(B, T, n_kv_heads, hd),
                     rope_base)
            v = (x @ w[f"l{i}.wv"]).reshape(B, T, n_kv_heads, hd)
            k = jnp.repeat(k, n_heads // n_kv_heads, axis=2)
            v = jnp.repeat(v, n_heads // n_kv_heads, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, D)
            h = h + a @ w[f"l{i}.wo"]
            x = rms_norm(h, w[f"l{i}.mlp_norm"], norm_eps)
            h = h + (jax.nn.silu(x @ w[f"l{i}.w_gate"])
                     * (x @ w[f"l{i}.w_up"])) @ w[f"l{i}.w_down"]
        return rms_norm(h, w["final_norm"], norm_eps) @ w["lm_head"]


def next_token_loss(weights, tokens, targets, **model):
    """Mean cross entropy of ``targets`` under forward()'s logits."""
    logp = jax.nn.log_softmax(forward(weights, tokens, **model), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
