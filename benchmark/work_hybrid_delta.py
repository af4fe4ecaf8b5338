"""Operations and bytes that a decoder of gated delta-rule layers and a
few full-attention layers, a dense SwiGLU in each, needs, from shapes
alone: the keys of the published config.json as configs/olmo-hybrid-7b.json
carries them (``num_hidden_layers`` and ``layer_types`` as held: the cut).

As work.py and work_hybrid_ssm.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST move, so a share of the roofline computed from them cannot pass 100%.
"""

CHUNK = 64      # positions a chunk of the rule (ops/delta_rule.py CHUNK)


def n_layers(m, attention):
    return sum(1 for t in m["layer_types"][:m["num_hidden_layers"]]
               if (t == "full_attention") == attention)


def head_dim(m):
    return m["hidden_size"] // m["num_attention_heads"]


def conv_channels(m):
    return m["linear_num_key_heads"] * 2 * m["linear_key_head_dim"] \
        + m["linear_num_value_heads"] * m["linear_value_head_dim"]


def swiglu_params(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def delta_params(m):
    """Matmul weights of one delta-rule mixer: the query, key and value
    projections, the output gate's, the output's, and the decay's and the
    write strength's a head."""
    D, H = m["hidden_size"], m["linear_num_value_heads"]
    value = H * m["linear_value_head_dim"]
    return D * (conv_channels(m) + value) + value * D + 2 * D * H


def delta_small_params(m):
    """What a delta-rule mixer holds besides: the convolution, A_log and
    the decay's bias, the gated norm."""
    return m["linear_conv_kernel_dim"] * conv_channels(m) \
        + 2 * m["linear_num_value_heads"] + m["linear_value_head_dim"]


def attention_params(m):
    D, H, G, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], head_dim(m))
    return 2 * D * H * hd + 2 * D * G * hd


def parameters(m):
    """Every parameter of the model as held: the embedding and the untied
    head, each layer's two norms, an attention layer's query and key
    norms."""
    D, H, G, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], head_dim(m))
    return n_layers(m, False) * (delta_params(m) + delta_small_params(m)
                                 + swiglu_params(m) + 2 * D) \
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m)
                               + 2 * D + (H + G) * hd) \
        + 2 * m["vocab_size"] * D + D


def state_entry_bytes(m, tail_bytes=2):
    """Bytes a request holds in ONE delta-rule layer whatever its length:
    the heads' states [H, dk, dv] in float32 and the convolution's last
    taps - 1 inputs."""
    return m["linear_num_value_heads"] * m["linear_key_head_dim"] \
        * m["linear_value_head_dim"] * 4 \
        + (m["linear_conv_kernel_dim"] - 1) * conv_channels(m) * tail_bytes


def state_bytes(m):
    """Bytes of state a request holds over all its delta-rule layers."""
    return n_layers(m, False) * state_entry_bytes(m)


def kv_entry_bytes(m, cache_bytes=2):
    """Bytes a position leaves in one attention layer: keys and values."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * cache_bytes


def rule_flops(m, prompt_len):
    """Operations of the chunked rule's PRODUCTS over one prompt in one
    layer, whole chunks of CHUNK positions (the last is padded): a chunk
    and head takes K K^T and Q K^T (C x C x dk each), K S, Q S and K^T U
    (C x dk x dv each), the solve's product and the output's (C x C x dv
    each)."""
    C, dk, dv = CHUNK, m["linear_key_head_dim"], m["linear_value_head_dim"]
    chunks = -(-prompt_len // C)
    return chunks * m["linear_num_value_heads"] * 2 * (
        2 * C * C * dk + 3 * C * dk * dv + 2 * C * C * dv)


def rule_elementwise_flops(m, prompt_len):
    """What the chunked rule does besides its products, NOT counted in
    ``prefill_flops``: the decays' exponentials and masks over a chunk's C
    x C pairs (4 arrays of them), the triangular system's inverse (its
    levels' products are C^3 / 3 in all) and the scaling of Q, K and the
    state (3 C dk + dk dv)."""
    C, dk, dv = CHUNK, m["linear_key_head_dim"], m["linear_value_head_dim"]
    chunks = -(-prompt_len // C)
    return chunks * m["linear_num_value_heads"] * (
        4 * C * C + 2 * C ** 3 // 3 + 3 * C * dk + dk * dv)


def prefill_flops(m, prompt_len):
    """Operations of the PRODUCTS to prefill one prompt: every real
    position through each layer's projections and its SwiGLU; the chunked
    rule's products (``rule_flops``) in the delta-rule layers; attention
    over the keys a position sees (t + 1 at position t, summed exactly),
    scores and values over head_dim, in the others; the head over the
    vocabulary once, for the last position."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    per_token = n_layers(m, False) * (delta_params(m) + swiglu_params(m)) \
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m))
    seen = prompt_len * (prompt_len + 1) // 2
    return 2 * prompt_len * per_token + 2 * D * m["vocab_size"] \
        + n_layers(m, False) * rule_flops(m, prompt_len) \
        + n_layers(m, True) * 2 * H * 2 * head_dim(m) * seen


def decode_step_bytes(m, state_updates, full_positions, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to move: every weight outside the
    embedding once (the layers' matrices, the head); the state entries of
    the live rows READ AND WRITTEN (``state_updates``: layers x live rows,
    as the programs count them: DELTA_STATS); and the keys and values
    attended (``full_positions``: summed over the live rows AND the
    attention layers). Activations, norms, the convolutions' weights and
    the rows' embedding lookups are thousands of times smaller and are
    left out."""
    fixed = weight_bytes * (
        n_layers(m, False) * (delta_params(m) + swiglu_params(m))
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m))
        + m["hidden_size"] * m["vocab_size"])
    return fixed + 2 * state_entry_bytes(m, cache_bytes) * state_updates \
        + kv_entry_bytes(m, cache_bytes) * full_positions
