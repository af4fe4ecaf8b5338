"""Operations and bytes that a decoder which mixes full and sliding-window
grouped-query attention layers, each kind with its own number of query
heads and a gate on every head, over routed experts ALL held beside a
shared one, needs, from shapes alone: the keys of the published config.json
as configs/laguna-xs.2.json carries them (``layer_types``,
``mlp_layer_types`` and ``num_attention_heads_per_layer`` among them).

As work.py and work_hybrid_share.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST read, so a share of the roofline computed from them cannot pass 100%.
"""
FULL, WINDOW = "full_attention", "sliding_attention"


def attention_params(m, i):
    """Matmul weights of layer ``i``'s attention: the query and output
    projections at the layer's own heads, keys and values, the gate."""
    D, H = m["hidden_size"], m["num_attention_heads_per_layer"][i]
    G, d = m["num_key_value_heads"], m["head_dim"]
    return 2 * D * H * d + 2 * D * G * d + D * H


def entry_bytes(m, cache_bytes=2):
    """Bytes a token leaves in one layer of either kind: [G, head_dim]
    keys and as much of values."""
    return cache_bytes * 2 * m["num_key_value_heads"] * m["head_dim"]


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m):
    return 3 * m["hidden_size"] * m["shared_expert_intermediate_size"]


def sparse_layers(m):
    return sum(1 for t in m["mlp_layer_types"] if t == "sparse")


def keys_attended(m, i, prompt_len):
    """Query-key pairs of one prompt in layer ``i``: the token at position
    t sees t + 1 keys, in a window layer at most sliding_window of them;
    the sum taken exactly."""
    if m["layer_types"][i] == WINDOW:
        w = min(m["sliding_window"], prompt_len)
        return w * (w + 1) // 2 + (prompt_len - w) * w
    return prompt_len * (prompt_len + 1) // 2


def prefill_flops(m, prompt_len):
    """Operations to prefill one prompt of ``prompt_len`` tokens: every
    token through each layer's attention projections and gate and its
    feed-forward (dense: the SwiGLU of intermediate_size; sparse: the
    router over all num_experts, num_experts_per_tok routed experts and
    the shared one); attention over the keys each layer's kind lets a
    token see, at the layer's own heads (scores and values over
    head_dim); the head over the vocabulary once, for the last position."""
    D, d = m["hidden_size"], m["head_dim"]
    total = 2 * D * m["vocab_size"]
    for i, mlp in enumerate(m["mlp_layer_types"]):
        ffn = 3 * D * m["intermediate_size"] if mlp == "dense" \
            else D * m["num_experts"] + m["num_experts_per_tok"] \
            * expert_params(m) + shared_params(m)
        total += 2 * prompt_len * (attention_params(m, i) + ffn) \
            + 2 * m["num_attention_heads_per_layer"][i] * 2 * d \
            * keys_attended(m, i, prompt_len)
    return total


def decode_step_parts(m, full_positions, window_positions, experts_touched,
                      weight_bytes=2, cache_bytes=2):
    """(other weights, routed experts, cache): the bytes one decode step
    has to read. Every weight outside the routed experts once (each
    layer's attention at its own heads, the dense layer's SwiGLU, the
    shared experts and the head in ``weight_bytes``; the routers in
    float32); the weights of the routed experts that a token reached
    (``experts_touched``: the mean number in one sparse layer); and the
    cache entries attended: ``full_positions`` and ``window_positions``
    (each summed over the active rows AND over the layers of its kind, as
    the programs count them: HYBRID_STATS) x an entry. Activations, norms
    and the rows' embedding lookups are thousands of times smaller and are
    left out, and so is the view of the rings a dispatch gathers, which no
    step MUST read."""
    D = m["hidden_size"]
    fixed = weight_bytes * D * m["vocab_size"]
    for i, mlp in enumerate(m["mlp_layer_types"]):
        fixed += weight_bytes * attention_params(m, i)
        fixed += weight_bytes * 3 * D * m["intermediate_size"] \
            if mlp == "dense" \
            else 4 * D * m["num_experts"] + weight_bytes * shared_params(m)
    experts = weight_bytes * sparse_layers(m) * experts_touched \
        * expert_params(m)
    cache = entry_bytes(m, cache_bytes) * (full_positions
                                           + window_positions)
    return fixed, experts, cache


def decode_step_bytes(m, full_positions, window_positions, experts_touched,
                      weight_bytes=2, cache_bytes=2):
    return sum(decode_step_parts(m, full_positions, window_positions,
                                 experts_touched, weight_bytes, cache_bytes))


def few_rows_call(m, experts_touched, rows, weight_bytes=2):
    """(operations, bytes) of ONE call of the kernel ``moe_few_rows`` (a
    decode step's routed experts in one sparse layer, ops/moe.py): every
    expert that a row reached is read once and multiplied with ALL the
    ``rows`` of the step, which is how the kernel is defined (a row's
    own picks alone would be num_experts_per_tok experts a row: fewer
    operations, the same bytes, and the bytes bound it)."""
    flops = 2 * rows * experts_touched * expert_params(m)
    return flops, weight_bytes * experts_touched * expert_params(m)
