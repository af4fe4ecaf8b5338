"""Operations and bytes that a decoder which mixes full and sliding-window
grouped-query attention layers, with routed experts, needs when it is one
chip's share of an expert-parallel layer, from shapes alone: the keys of
the published config.json as configs/mimo-v2-flash-ep16.json carries them
(the layer pattern and the dense/routed pattern among them), the experts
held (``experts_held.count``, which is the file's ``n_routed_experts``)
and the router's published width (``experts_held.of``).

As work.py and work_latent_share.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST read, so a share of the roofline computed from them cannot pass 100%.
"""
FULL, WINDOW = 0, 1


def kv_heads(m, kind):
    return m["swa_num_key_value_heads"] if kind == WINDOW \
        else m["num_key_value_heads"]


def attention_params(m, kind):
    """Matmul weights of one attention layer of ``kind``."""
    D, H, G = m["hidden_size"], m["num_attention_heads"], kv_heads(m, kind)
    kd, vd = m["head_dim"], m["v_head_dim"]
    return D * H * kd + D * G * (kd + vd) + H * vd * D


def entry_bytes(m, kind, cache_bytes=2):
    """Bytes a token leaves in one layer of ``kind``: [G, head_dim] keys
    and [G, v_head_dim] values."""
    return cache_bytes * kv_heads(m, kind) * (m["head_dim"]
                                              + m["v_head_dim"])


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layers(m):
    """[(attention kind, routed)] of the layers as run."""
    return list(zip(m["hybrid_layer_pattern"],
                    (bool(r) for r in m["moe_layer_freq"])))


def keys_attended(m, kind, prompt_len):
    """Query-key pairs of one prompt in one layer of ``kind``: the token
    at position t sees t + 1 keys, in a window layer at most
    sliding_window of them; the sum taken exactly."""
    if kind == WINDOW:
        w = min(m["sliding_window"], prompt_len)
        return w * (w + 1) // 2 + (prompt_len - w) * w
    return prompt_len * (prompt_len + 1) // 2


def prefill_flops(m, prompt_len, held_share):
    """Operations to prefill one prompt of ``prompt_len`` tokens on this
    chip: every token through each layer's attention projections and its
    feed-forward (dense: the SwiGLU of intermediate_size; routed: the
    router over all ``experts_held.of`` experts and num_experts_per_tok x
    ``held_share`` routed experts: the share of a token's picks that fell
    on experts held here, measured, 1/16 under an even router); attention
    over the keys each layer's kind lets a token see (scores over
    head_dim, values over v_head_dim); the head over the vocabulary's
    slice once, for the last position."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    total = 2 * D * m["vocab_size"]
    for kind, routed in layers(m):
        ffn = D * m["experts_held"]["of"] + m["num_experts_per_tok"] \
            * held_share * expert_params(m) if routed \
            else 3 * D * m["intermediate_size"]
        total += 2 * prompt_len * (attention_params(m, kind) + ffn) \
            + 2 * H * (m["head_dim"] + m["v_head_dim"]) \
            * keys_attended(m, kind, prompt_len)
    return total


def decode_step_bytes(m, full_positions, window_positions, experts_touched,
                      weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read on this chip: every weight
    outside the routed experts once (attention of each layer's kind, the
    dense layers' SwiGLU, the head's slice in ``weight_bytes``; the
    router, all ``experts_held.of`` columns, in float32), the weights of
    the HELD experts that a token reached (``experts_touched``: the mean
    number in one routed layer), and the cache entries attended:
    ``full_positions`` and ``window_positions`` (each summed over the
    active rows AND over the layers of its kind, as the programs count
    them: HYBRID_STATS) x that kind's entry. Activations, norms, sinks and
    the rows' embedding lookups are thousands of times smaller and are
    left out."""
    D = m["hidden_size"]
    fixed = weight_bytes * D * m["vocab_size"]
    routed = 0
    for kind, is_routed in layers(m):
        fixed += weight_bytes * attention_params(m, kind)
        if is_routed:
            routed += 1
            fixed += 4 * D * m["experts_held"]["of"]
        else:
            fixed += weight_bytes * 3 * D * m["intermediate_size"]
    experts = weight_bytes * routed * experts_touched * expert_params(m)
    cache = entry_bytes(m, FULL, cache_bytes) * full_positions \
        + entry_bytes(m, WINDOW, cache_bytes) * window_positions
    return fixed + experts + cache
