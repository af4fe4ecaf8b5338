"""Operations and bytes that a decoder of Mamba-1 layers (with the Jamba
family's inner norms) and a few grouped-query attention layers, a dense
SwiGLU in each, needs, from shapes alone: the keys of the published
config.json as configs/ai21-jamba2-3b.json carries them.

As work.py and work_hybrid_share.py: a multiply-add counts as 2 operations,
only what the algorithm has to do is counted, and only bytes that a step
MUST move, so a share of the roofline computed from them cannot pass 100%.
"""


def d_inner(m):
    return m["mamba_expand"] * m["hidden_size"]


def is_attention(m, i):
    return i % m["attn_layer_period"] == m["attn_layer_offset"]


def n_layers(m, attention):
    return sum(1 for i in range(m["num_hidden_layers"])
               if is_attention(m, i) == attention)


def head_dim(m):
    return m["hidden_size"] // m["num_attention_heads"]


def swiglu_params(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def mamba_params(m):
    """Matmul weights of one Mamba mixer: in_proj, x_proj, dt_proj,
    out_proj."""
    D, C = m["hidden_size"], d_inner(m)
    R, N = m["mamba_dt_rank"], m["mamba_d_state"]
    return D * 2 * C + C * (R + 2 * N) + R * C + C * D


def mamba_small_params(m):
    """What a Mamba mixer holds besides: A_log, the convolution and its
    bias, D, the step's bias and the three inner norms."""
    C, N = d_inner(m), m["mamba_d_state"]
    return C * N + C * m["mamba_d_conv"] + 3 * C \
        + m["mamba_dt_rank"] + 2 * N


def attention_params(m):
    D, H, G, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], head_dim(m))
    return 2 * D * H * hd + 2 * D * G * hd


def parameters(m):
    """Every parameter of the model, the tied embedding once."""
    D = m["hidden_size"]
    return n_layers(m, False) * (mamba_params(m) + mamba_small_params(m)
                                 + swiglu_params(m) + 2 * D) \
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m)
                               + 2 * D) \
        + m["vocab_size"] * D + D


def state_entry_bytes(m, tail_bytes=2):
    """Bytes a request holds in ONE Mamba layer whatever its length: the
    recurrent state [d_inner, d_state] in float32 and the convolution's
    last d_conv - 1 inputs."""
    return d_inner(m) * m["mamba_d_state"] * 4 \
        + (m["mamba_d_conv"] - 1) * d_inner(m) * tail_bytes


def state_bytes(m):
    """Bytes of state a request holds over all its Mamba layers."""
    return n_layers(m, False) * state_entry_bytes(m)


def kv_entry_bytes(m, cache_bytes=2):
    """Bytes a position leaves in one attention layer: keys and values."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * cache_bytes


def prefill_flops(m, prompt_len):
    """Operations of the PRODUCTS to prefill one prompt: every real
    position through each layer's projections and its SwiGLU; attention
    over the keys a position sees (t + 1 at position t, summed exactly),
    scores and values over head_dim; the head over the vocabulary once,
    for the last position. The recurrence's elementwise work is not a
    product and is ``scan_flops``."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    per_token = n_layers(m, False) * (mamba_params(m) + swiglu_params(m)) \
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m))
    seen = prompt_len * (prompt_len + 1) // 2
    return 2 * prompt_len * per_token + 2 * D * m["vocab_size"] \
        + n_layers(m, True) * 2 * H * 2 * head_dim(m) * seen


def scan_flops(m, prompt_len):
    """Elementwise operations of the recurrence over one prompt, apart
    from the products: a state element a position takes the decay's
    exponent and exponential, its product with the state, the input's
    product and sum, and the output's product and sum (7)."""
    return 7 * n_layers(m, False) * prompt_len * d_inner(m) \
        * m["mamba_d_state"]


def decode_step_bytes(m, state_updates, full_positions, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to move: every weight once (the layers'
    matrices in ``weight_bytes``, the Mamba layers' float32 vectors, the
    head, which is the embedding, once); the state entries of the live
    rows READ AND WRITTEN (``state_updates``: layers x live rows, as the
    programs count them: SSM_STATS); and the keys and values attended
    (``full_positions``: summed over the live rows AND the attention
    layers). Activations, norms and the rows' embedding lookups are
    thousands of times smaller and are left out."""
    D = m["hidden_size"]
    fixed = weight_bytes * (
        n_layers(m, False) * (mamba_params(m) + swiglu_params(m))
        + n_layers(m, True) * (attention_params(m) + swiglu_params(m))
        + D * m["vocab_size"]) \
        + 4 * n_layers(m, False) * d_inner(m) * (m["mamba_d_state"] + 2)
    return fixed + 2 * state_entry_bytes(m, cache_bytes) * state_updates \
        + kv_entry_bytes(m, cache_bytes) * full_positions
