"""Operations and bytes that a decoder of gated short-convolution layers
beside a few grouped-query attention layers, over routed experts ALL held
behind leading dense layers, needs, from shapes alone: the keys of the
published config.json as configs/lfm2-24b-a2b.json carries them
(``layer_types``, ``num_dense_layers``, ``conv_L_cache``) and ``head_dim``.

As work.py and work_hybrid_gated.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST move, so a share of the roofline computed from them cannot pass 100%.
"""
FULL, CONV = "full_attention", "conv"


def mixer_params(m, i):
    """Matmul weights of layer ``i``'s operator: an attention layer's four
    projections, or a conv layer's in_proj (three times the width) and
    out_proj; the taps and the norms a head are thousands of times
    smaller."""
    D = m["hidden_size"]
    if m["layer_types"][i] == CONV:
        return 3 * D * D + D * D
    H, G, d = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    return 2 * D * H * d + 2 * D * G * d


def layers_of(m, kind):
    return sum(1 for t in m["layer_types"] if t == kind)


def entry_bytes(m, cache_bytes=2):
    """Bytes a token leaves in one attention layer: [G, head_dim] keys and
    as much of values."""
    return cache_bytes * 2 * m["num_key_value_heads"] * m["head_dim"]


def tail_bytes(m, cache_bytes=2):
    """Bytes a SEQUENCE keeps in one conv layer, whatever its length: the
    last conv_L_cache - 1 inputs of hidden_size."""
    return cache_bytes * (m["conv_L_cache"] - 1) * m["hidden_size"]


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def routed_layers(m):
    return m["num_hidden_layers"] - m["num_dense_layers"]


def prefill_flops(m, prompt_len):
    """Operations to prefill one prompt of ``prompt_len`` tokens: every
    token through each layer's operator projections and its feed-forward
    (dense: the SwiGLU of intermediate_size; routed: the router over all
    num_experts and num_experts_per_tok experts); an attention layer's
    scores and values over the t + 1 keys token t sees, the sum taken
    exactly; a conv layer's taps and gates (a few operations a width);
    the head over the vocabulary once, for the last position."""
    D, d = m["hidden_size"], m["head_dim"]
    total = 2 * D * m["vocab_size"]
    for i, kind in enumerate(m["layer_types"]):
        ffn = 3 * D * m["intermediate_size"] \
            if i < m["num_dense_layers"] \
            else D * m["num_experts"] + m["num_experts_per_tok"] \
            * expert_params(m)
        total += 2 * prompt_len * (mixer_params(m, i) + ffn)
        if kind == CONV:
            total += prompt_len * D * (2 * m["conv_L_cache"] + 2)
        else:
            total += 2 * m["num_attention_heads"] * 2 * d \
                * prompt_len * (prompt_len + 1) // 2
    return total


def decode_step_parts(m, positions, rows, experts_touched, weight_bytes=2,
                      cache_bytes=2):
    """(other weights, routed experts, cache, logits): the bytes one
    decode step has to move. Every weight outside the routed experts once
    (each layer's operator, the dense layers' SwiGLU and the head in
    ``weight_bytes``; the routers in float32); the weights of the routed
    experts that a token reached (``experts_touched``: the mean number in
    one routed layer); the cache: the entries attended (``positions``:
    summed over the active rows AND the attention layers, as the programs
    count them, HYBRID_STATS) and every active row's tail in every conv
    layer, read and written (``rows``: summed over the rows AND the conv
    layers, CONV_STATS); and the step's float32 logits, one row of the
    vocabulary an active row, which the engine hands back. Activations and
    norms are thousands of times smaller and are left out."""
    D = m["hidden_size"]
    fixed = weight_bytes * D * m["vocab_size"]
    for i in range(m["num_hidden_layers"]):
        fixed += weight_bytes * mixer_params(m, i)
        fixed += weight_bytes * 3 * D * m["intermediate_size"] \
            if i < m["num_dense_layers"] else 4 * D * m["num_experts"]
    experts = weight_bytes * routed_layers(m) * experts_touched \
        * expert_params(m)
    conv = layers_of(m, CONV)
    cache = entry_bytes(m, cache_bytes) * positions \
        + 2 * tail_bytes(m, cache_bytes) * rows
    logits = 4 * m["vocab_size"] * (rows / conv if conv else 0)
    return fixed, experts, cache, logits


def grouped_rows_call(m, experts_touched, pairs, weight_bytes=2):
    """(operations, bytes) of ONE call of the kernel ``moe_grouped_rows``
    (the sorted pairs of one routed layer, ops/moe.py): every expert that
    a pair reached is read once, whole, and multiplied with its own rows
    alone; the rows go in in ``weight_bytes`` and come out in float32."""
    D = m["hidden_size"]
    return (2 * pairs * expert_params(m),
            weight_bytes * experts_touched * expert_params(m)
            + pairs * D * (weight_bytes + 4))


def attn_decode_call(m, positions, cache_bytes=2):
    """Bytes ONE call of the kernel ``paged_flat_packed_decode`` (a decode
    step's attention in one layer, ops/pallas_attention.py) has to read:
    the entries of the ``positions`` its rows attend, keys and values."""
    return entry_bytes(m, cache_bytes) * positions
