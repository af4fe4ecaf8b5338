"""Operations and bytes that a latent-attention / routed-experts /
hyper-connection decoder needs, from shapes alone (the keys of the
published config.json, as configs/xing4.0-29b-a4b.json carries them).

As work.py: a multiply-add counts as 2 operations, and only what the
algorithm has to do is counted. Only bytes that a step MUST read are
counted, so a share of the roofline computed from them cannot pass 100%.
"""


def _sizes(m):
    D, H = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    R, Q = m["kv_lora_rank"], m["q_lora_rank"]
    n = m["hc_mult"]
    return D, H, nope, rope, vd, R, Q, n


def attention_params(m):
    """Matmul weights of one layer's latent attention."""
    D, H, nope, rope, vd, R, Q, _ = _sizes(m)
    return (D * Q + Q * H * (nope + rope) + D * (R + rope)
            + R * H * (nope + vd) + H * vd * D)


def mixing_params(m):
    """One layer's hyper-connection projections (two sublayers), float32."""
    D, n = m["hidden_size"], m["hc_mult"]
    return 2 * n * D * (2 * n + n * n)


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_counts(m):
    """(dense layers, routed layers) as run."""
    dense = m["first_k_dense_replace"]
    return dense, m["num_hidden_layers"] - dense


def prefill_flops(m, prompt_len):
    """Operations to prefill one prompt of ``prompt_len`` tokens: every
    token through each layer's attention projections, its feed-forward
    (dense: the SwiGLU; routed: the router, num_experts_per_tok experts
    and the shared ones) and the hyper-connection projections; causal
    attention of the token at position t over t + 1 keys (scores over
    qk_nope + qk_rope, values over v_head_dim), the sum taken exactly;
    the head once, for the last position. A chunked prefill expands
    cached latents again in every later chunk: recomputed, not counted."""
    D, H, nope, rope, vd, R, Q, n = _sizes(m)
    dense, routed = layer_counts(m)
    per_token_dense = attention_params(m) + mixing_params(m) \
        + 3 * D * m["intermediate_size"]
    per_token_routed = attention_params(m) + mixing_params(m) \
        + D * m["n_routed_experts"] \
        + (m["num_experts_per_tok"] + m["n_shared_experts"]) \
        * expert_params(m)
    keys = prompt_len * (prompt_len + 1) // 2
    attend = 2 * H * (nope + rope + vd) * keys
    return (2 * prompt_len * (dense * per_token_dense
                              + routed * per_token_routed)
            + (dense + routed) * attend + 2 * D * m["vocab_size"])


def decode_step_bytes(m, positions, experts_touched, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to read: every weight outside the routed
    experts once (attention, shared experts, the dense layers' SwiGLU,
    the head in ``weight_bytes``; router and hyper-connection projections
    in float32), the weights of the experts that a token reached
    (``experts_touched``: the mean number in one routed layer), and the
    cache entries attended: ``positions`` (summed over the active rows) x
    layers x (kv_lora_rank + qk_rope_head_dim) x ``cache_bytes``.
    Activations, norms and the rows' embedding lookups are thousands of
    times smaller and are left out."""
    D = m["hidden_size"]
    dense, routed = layer_counts(m)
    fixed = weight_bytes * (
        (dense + routed) * attention_params(m)
        + dense * 3 * D * m["intermediate_size"]
        + routed * m["n_shared_experts"] * expert_params(m)
        + D * m["vocab_size"])
    fixed += 4 * ((dense + routed) * mixing_params(m)
                  + routed * D * m["n_routed_experts"])
    experts = weight_bytes * routed * experts_touched * expert_params(m)
    cache = cache_bytes * positions * (dense + routed) \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return fixed + experts + cache
