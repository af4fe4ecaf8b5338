"""Operations and bytes that a latent-attention / routed-experts decoder
on the plain residual path needs when it is one chip's share of an
expert-parallel layer, from shapes alone: the keys of the published
config.json as configs/deepseek-v3-ep16.json carries them, the experts
held (``experts_held.count``, which is the file's ``n_routed_experts``)
and the router's published width (``experts_held.of``).

As work.py and work_latent_moe.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST read, so a share of the roofline computed from them cannot pass 100%.
"""
from benchmark.work_latent_moe import expert_params, layer_counts


def attention_params(m):
    """Matmul weights of one layer's latent attention."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    R, Q = m["kv_lora_rank"], m["q_lora_rank"]
    return (D * Q + Q * H * (nope + rope) + D * (R + rope)
            + R * H * (nope + vd) + H * vd * D)


def prefill_flops(m, prompt_len, held_share):
    """Operations to prefill one prompt of ``prompt_len`` tokens on this
    chip: every token through each layer's attention projections and its
    feed-forward (dense: the SwiGLU; routed: the router over all
    ``experts_held.of`` experts, the shared experts, and
    num_experts_per_tok x ``held_share`` routed experts: the share of a
    token's picks that fell on experts held here, measured, 1/16 under an
    even router); causal attention of the token at position t over t + 1
    keys (scores over qk_nope + qk_rope, values over v_head_dim), the sum
    taken exactly; the head over the vocabulary's slice once, for the
    last position."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    dense, routed = layer_counts(m)
    per_token_dense = attention_params(m) + 3 * D * m["intermediate_size"]
    per_token_routed = attention_params(m) + D * m["experts_held"]["of"] \
        + (m["num_experts_per_tok"] * held_share + m["n_shared_experts"]) \
        * expert_params(m)
    keys = prompt_len * (prompt_len + 1) // 2
    attend = 2 * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                      + m["v_head_dim"]) * keys
    return (2 * prompt_len * (dense * per_token_dense
                              + routed * per_token_routed)
            + (dense + routed) * attend + 2 * D * m["vocab_size"])


def decode_step_bytes(m, positions, experts_touched, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to read on this chip: every weight
    outside the routed experts once (attention, shared experts, the dense
    layers' SwiGLU, the head's slice in ``weight_bytes``; the router, all
    ``experts_held.of`` columns, in float32), the weights of the HELD
    experts that a token reached (``experts_touched``: the mean number in
    one routed layer), and the cache entries attended: ``positions``
    (summed over the active rows) x layers x (kv_lora_rank +
    qk_rope_head_dim) x ``cache_bytes``. Activations, norms and the rows'
    embedding lookups are thousands of times smaller and are left out."""
    D = m["hidden_size"]
    dense, routed = layer_counts(m)
    fixed = weight_bytes * (
        (dense + routed) * attention_params(m)
        + dense * 3 * D * m["intermediate_size"]
        + routed * m["n_shared_experts"] * expert_params(m)
        + D * m["vocab_size"])
    fixed += 4 * routed * D * m["experts_held"]["of"]
    experts = weight_bytes * routed * experts_touched * expert_params(m)
    cache = cache_bytes * positions * (dense + routed) \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return fixed + experts + cache
