"""Operations and bytes that a looped decoder needs (one stack of dense
layers run ``total_ut_steps`` times a token, every pass with keys and
values of its own), from shapes alone: the keys of the published
config.json as configs/ouro-2.6b.json carries them.

As work.py and work_hybrid_ssm.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST move, so a share of the roofline computed from them cannot pass 100%.
"""


def passes(m):
    return m["total_ut_steps"]


def layer_params(m):
    """Matmul weights of one layer: q and o, k and v, the SwiGLU."""
    D, H, G, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    return 2 * D * H * hd + 2 * D * G * hd + 3 * D * m["intermediate_size"]


def parameters(m):
    """Every parameter of the model: the layers with their four norms,
    the embedding and the untied head, the final norm, the exit gate."""
    D = m["hidden_size"]
    return m["num_hidden_layers"] * (layer_params(m) + 4 * D) \
        + 2 * m["vocab_size"] * D + D + D + 1


def kv_entry_bytes(m, cache_bytes=2):
    """Bytes a position leaves in ONE cache layer: keys and values."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * cache_bytes


def cache_layers(m):
    """Cache layers a position leaves an entry in: one a layer a pass."""
    return passes(m) * m["num_hidden_layers"]


def cache_bytes_per_position(m, cache_bytes=2):
    return cache_layers(m) * kv_entry_bytes(m, cache_bytes)


def prefill_flops(m, prompt_len):
    """Operations to prefill one prompt: every position through every
    layer's projections and SwiGLU, ONCE A PASS; attention over the keys a
    position sees (t + 1 at position t, summed exactly), scores and values
    over head_dim, in every layer of every pass; the head over the
    vocabulary once, for the last position."""
    H, hd = m["num_attention_heads"], m["head_dim"]
    seen = prompt_len * (prompt_len + 1) // 2
    return cache_layers(m) * (2 * prompt_len * layer_params(m)
                              + 2 * H * 2 * hd * seen) \
        + 2 * m["hidden_size"] * m["vocab_size"]


def decode_step_bytes(m, positions_attended, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to move: the layers' matrices ONCE A
    PASS (the same weights, streamed again: nothing holds 4.9 GB between
    passes), the head once, and the keys and values attended
    (``positions_attended``: summed over the live rows AND the passes x
    layers cache layers, as the programs count them: LOOP_STATS).
    Activations, norms, the gate and the rows' embedding lookups are
    thousands of times smaller and are left out."""
    return weight_bytes * (passes(m) * m["num_hidden_layers"]
                           * layer_params(m)
                           + m["hidden_size"] * m["vocab_size"]) \
        + kv_entry_bytes(m, cache_bytes) * positions_attended
