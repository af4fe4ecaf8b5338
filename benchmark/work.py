"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick's, kept here so that no PR that claims a gain can
change them. A multiply-add counts as 2 operations, as the chip's published
peak does. Recomputed operations (remat) are never counted.
"""


def llama_matmul_params(model):
    """Weights that every token is multiplied by: (one layer's, the
    head's). The embedding is a row lookup and does not count."""
    D, hd = model["hidden_size"], model["head_dim"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    F, V = model["intermediate_size"], model["vocab_size"]
    layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return layer, D * V


def llama_train_flops_per_token(model, seq):
    """Forward and backward operations a training token needs: 3 x the
    forward's (2 per weight, plus causal attention over on average seq/2
    keys: QK^T and PV, 2 x 2 x seq/2 x heads x head_dim a layer)."""
    layer, head = llama_matmul_params(model)
    L = model["num_hidden_layers"]
    attn = 2 * seq * model["num_attention_heads"] * model["head_dim"]
    return 3 * (2 * (L * layer + head) + L * attn)


def llama_decode_step_bytes(model, rows, mean_len, weight_bytes=1,
                            kv_bytes=2):
    """Bytes one decode step has to read: every matmul weight once (int8:
    1 byte, plus its float32 per-channel scales), and the K and V of each
    active row at its own length. Activations, the embedding rows and the
    norms are thousands of times smaller and are left out. Decode is
    bandwidth-bound: at 16 rows the matmuls need 2 x 16 operations a
    weight byte, far under the chip's 240 operations a byte."""
    layer, head = llama_matmul_params(model)
    L = model["num_hidden_layers"]
    D, hd = model["hidden_size"], model["head_dim"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    F, V = model["intermediate_size"], model["vocab_size"]
    weights = (L * layer + head) * weight_bytes
    scales = 0
    if weight_bytes == 1:
        scales = 4 * (L * (H * hd + 2 * KV * hd + D + 2 * F + D) + V)
    kv = rows * mean_len * 2 * L * KV * hd * kv_bytes
    return weights + scales + kv


def resnet50_forward_macs(image_size=224, classes=1000):
    """Multiply-adds of one ResNet-50 forward pass as models/resnet.py
    builds it (He et al. 2015, table 1: the stride of a stage's first
    unit sits on its first 1x1). Convolutions and the classifier only."""
    def conv(hw, cin, cout, k, stride):
        out = -(-hw // stride)
        return out, out * out * cin * cout * k * k

    hw, macs = conv(image_size, 3, 64, 7, 2)
    hw = -(-hw // 2)                                  # 3x3/2 max pool
    cin = 64
    for width, count in ((64, 3), (128, 4), (256, 6), (512, 3)):
        for i in range(count):
            stride = 2 if (i == 0 and width != 64) else 1
            if cin != width * 4 or stride != 1:
                macs += conv(hw, cin, width * 4, 1, stride)[1]
            hw2, m = conv(hw, cin, width, 1, stride)
            macs += m
            macs += conv(hw2, width, width, 3, 1)[1]
            macs += conv(hw2, width, width * 4, 1, 1)[1]
            hw, cin = hw2, width * 4
    return macs + cin * classes


def resnet50_train_flops_per_image(image_size=224, classes=1000):
    """3 x the forward's operations (backward: one pass for the inputs'
    gradients, one for the weights'), 2 operations a multiply-add."""
    return 3 * 2 * resnet50_forward_macs(image_size, classes)


def train_flops_per_item(config):
    """Operations per item (image or token) of a training configuration."""
    b = config["builder"]
    if b["model"] == "resnet50":
        return resnet50_train_flops_per_image(
            config["image_size"], config["num_classes"])
    if b["model"] == "llama":
        return llama_train_flops_per_token(config, b["seq"])
    raise ValueError(f"no shape function for model {b['model']!r}")
