"""Work counts from shapes: parameters and training FLOPs of the cells'
models, computed from their configuration files alone.

A FLOP is one multiply or one add, so a multiply-accumulate is 2. Training
counts the forward pass and a backward pass of twice its cost. Only the
matrix products count: for the transformer the dense layers, the head and
the causal attention products (half of the score matrix), not the embedding
gather, the norms or the softmax.
"""
from __future__ import annotations


def transformer_matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product per token: the attention and MLP
    kernels of every layer and the head."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    attn = d * heads * hd * 2 + d * kv * hd * 2          # q, o; k, v
    mlp = 3 * d * ff                                      # gate, up, down
    return cfg["num_hidden_layers"] * (attn + mlp) + d * v


def transformer_params(cfg: dict, norms: bool = True) -> int:
    """All parameters of one replica: the matmul weights, the embedding and,
    with ``norms``, the LayerNorm scales and biases (two per layer and the
    final one)."""
    d = cfg["hidden_size"]
    total = transformer_matmul_params(cfg) + cfg["vocab_size"] * d
    if norms:
        total += (2 * cfg["num_hidden_layers"] + 1) * 2 * d
    return total


def transformer_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token at sequence length ``seq_len``:
    2 per matmul weight, plus the causal attention products (QK^T and AV
    over on average ``seq_len / 2`` keys), all times 3."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    forward = 2 * transformer_matmul_params(cfg) + layers * 2 * 2 * (seq_len / 2) * d
    return 3.0 * forward


def cnn_train_flops_per_sample(cfg: dict) -> float:
    """Forward plus backward FLOPs of the paper's CNN per image: valid 2-D
    convolutions each followed by 2x2 pooling, then the dense layers."""
    c, h, w = cfg["input"]
    macs = 0
    layers = cfg["layers"]
    for name in ("conv1", "conv2"):
        k = layers[name]
        if k["in_channels"] != c:
            raise ValueError(f"{name} takes {k['in_channels']} channels, gets {c}")
        h, w = h - k["kernel"] + 1, w - k["kernel"] + 1
        macs += h * w * k["out_channels"] * c * k["kernel"] ** 2
        c, h, w = k["out_channels"], h // 2, w // 2
    for name in ("fc1", "fc2"):
        macs += layers[name]["in_features"] * layers[name]["out_features"]
    return 3.0 * 2 * macs


def cnn_params(cfg: dict) -> int:
    layers = cfg["layers"]
    total = 0
    for name in ("conv1", "conv2"):
        k = layers[name]
        total += k["out_channels"] * (k["in_channels"] * k["kernel"] ** 2 + 1)
    for name in ("fc1", "fc2"):
        total += layers[name]["out_features"] * (layers[name]["in_features"] + 1)
    return total
