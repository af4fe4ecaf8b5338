"""Operations and bytes that a decoder of Kimi-delta-attention layers (a
matrix state a head, a decay a channel) beside latent-attention layers,
over a share of each layer's routed experts and a shared expert behind
leading dense layers, needs on this chip, from shapes alone: the keys of
the published config.json as configs/ling-3.0-flash-ep4.json carries them
(``layer_types``, ``first_k_dense_replace``, ``short_conv_kernel_size``),
the experts held (``experts_held.count``) and the router's published width
(``experts_held.of``).

As work.py and work_latent_share.py: a multiply-add counts as 2
operations, only what the algorithm has to do is counted, and only bytes
that a step MUST move, so a share of the roofline computed from them cannot
pass 100%.
"""
KDA, MLA = "kda", "mla"
CHUNK = 64      # positions a chunk of the rule (ops/delta_rule.py)


def mixer_params(m, i):
    """Matmul weights of layer ``i``'s mixer. kda: the query, key, value,
    decay and output-gate projections and the output, each hidden x heads x
    head_dim, and the write strength's hidden x heads. mla: the query
    (no low-rank pair), the latent and rotated key, the expansion, the
    gate a head and the output."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    if m["layer_types"][i] == KDA:
        return 6 * D * H * m["head_dim"] + D * H
    nope, rope, vd, R = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"], m["kv_lora_rank"])
    return (D * H * (nope + rope) + D * (R + rope) + R * H * (nope + vd)
            + D * H + H * vd * D)


def layers_of(m, kind):
    return sum(1 for t in m["layer_types"] if t == kind)


def routed_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def state_bytes(m, tail_bytes=2):
    """Bytes a SEQUENCE keeps in one kda layer, whatever its length: the
    heads' [head_dim, head_dim] states in float32 and the convolution's
    last short_conv_kernel_size - 1 inputs of its 3 x heads x head_dim
    channels."""
    H, d = m["num_attention_heads"], m["head_dim"]
    return 4 * H * d * d \
        + tail_bytes * (m["short_conv_kernel_size"] - 1) * 3 * H * d


def entry_bytes(m, cache_bytes=2):
    """Bytes a token leaves in one latent layer: the latent and the
    rotated key (the pool stores them at whole lane tiles, 640 wide; the
    64 values of padding are not work)."""
    return cache_bytes * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def prefill_flops(m, prompt_len, held_share):
    """Operations to prefill one prompt of ``prompt_len`` tokens on this
    chip: every token through each layer's mixer projections and its
    feed-forward (dense: the SwiGLU of intermediate_size; routed: the
    router over all ``experts_held.of`` experts, the shared expert, and
    num_experts_per_tok x ``held_share`` routed experts: the share of a
    token's picks that fell on experts held here, measured, 1/4 under an
    even router); a kda layer's rule 64 positions at a time (a head a
    position: three products with the [d, d] state, the chunk's pair sums
    of keys with keys and queries with keys and its two triangular
    products, each the causal half of C x d: 3 d d + 2 C d multiply-adds,
    which at C = 64, d = 128 is the recurrence's own 4 d d) and its taps;
    a latent layer's scores and values over the t + 1 keys token t sees
    (scores over qk_nope + qk_rope, values over v_head_dim), the sum taken
    exactly, beside the
    expansion of its own latent; the head over the vocabulary's slice
    once, for the last position."""
    D, H, d = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    total = 2 * D * m["vocab_size"]
    keys = prompt_len * (prompt_len + 1) // 2
    for i, kind in enumerate(m["layer_types"]):
        ffn = 3 * D * m["intermediate_size"] \
            if i < m["first_k_dense_replace"] \
            else D * m["experts_held"]["of"] \
            + (m["num_experts_per_tok"] * held_share + 1) * expert_params(m)
        total += 2 * prompt_len * (mixer_params(m, i) + ffn)
        if kind == KDA:
            total += 2 * prompt_len * H * (3 * d * d + 2 * CHUNK * d) \
                + 2 * prompt_len * 3 * H * d * m["short_conv_kernel_size"]
        else:
            total += 2 * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                              + m["v_head_dim"]) * keys
    return total


def decode_step_parts(m, state_updates, latent_positions, experts_touched,
                      weight_bytes=2, cache_bytes=2):
    """(other weights, held experts, state, latent pages): the bytes one
    decode step has to move on this chip. Every weight outside the routed
    experts once (each layer's mixer, the dense layers' SwiGLU, the shared
    experts and the head's slice in ``weight_bytes``; the routers, all
    ``experts_held.of`` columns, in float32); the weights of the HELD
    experts that a token reached (``experts_touched``: the mean number in
    one routed layer); the state entries of the live rows READ AND WRITTEN
    (``state_updates``: kda layers x live rows, as the programs count
    them: KDA_LATENT_STATS); and the latent entries attended
    (``latent_positions``: summed over the live rows AND the latent
    layers). The loop's decode program hands back no logits (PR 59).
    Activations, norms, the taps and the rows' embedding lookups are
    thousands of times smaller and are left out."""
    D = m["hidden_size"]
    fixed = weight_bytes * D * m["vocab_size"]
    for i in range(m["num_hidden_layers"]):
        fixed += weight_bytes * mixer_params(m, i)
        fixed += weight_bytes * 3 * D * m["intermediate_size"] \
            if i < m["first_k_dense_replace"] \
            else 4 * D * m["experts_held"]["of"] \
            + weight_bytes * expert_params(m)
    experts = weight_bytes * routed_layers(m) * experts_touched \
        * expert_params(m)
    state = 2 * state_bytes(m, cache_bytes) * state_updates
    latent = entry_bytes(m, cache_bytes) * latent_positions
    return fixed, experts, state, latent
