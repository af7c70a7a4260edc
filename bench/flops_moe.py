"""Work counts from shapes for the DeepSeek-V2 cells: training FLOPs of what
one chip computes, and FLOPs and bytes of the grouped expert matmul,
computed from the configuration file alone.

A FLOP is one multiply or one add, so a multiply-accumulate is 2. Training
counts the forward pass and a backward pass of twice its cost. Only the
matrix products count (the dense layers, the router, the experts, the head
and the causal attention products over on average ``seq_len / 2`` keys),
not the embedding gather, the norms, the softmax or the dispatch.

The experts this chip computes are counted from the expected routed pairs
and not from what a run routed: a token picks ``num_experts_per_tok`` of
the router's ``router_width`` experts, ``n_routed_experts`` of which are
held here, so a node-round of T tokens sends T·k·held/E pairs to them. The
grouped matmul's bytes are what each of its products must move at least:
its rows in, the held experts' weights in, its result out, in bfloat16.
"""
from __future__ import annotations


def held_pairs_per_token(cfg: dict) -> float:
    """Expected (token, expert) pairs per token routed to the held experts."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]


def _mla_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (r + rope) + r * h * nope + r * h * dv
            + h * dv * d)


def chip_matmul_params_per_token(cfg: dict) -> float:
    """Weights that enter a matrix product per token on this chip: MLA in
    every layer, the dense SwiGLU of the leading layers, the router, shared
    experts and expected held-expert pairs of the others, the head over the
    vocabulary slice."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    per_moe = (d * cfg["router_width"] + 3 * d * f * cfg["n_shared_experts"]
               + held_pairs_per_token(cfg) * 3 * d * f)
    return (cfg["num_hidden_layers"] * _mla_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"] + moe * per_moe
            + d * cfg["vocab_slice"])


def chip_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token of what this chip computes."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = cfg["num_hidden_layers"] * 2 * h * (qk + cfg["v_head_dim"]) * (seq_len / 2)
    return 3.0 * (2 * chip_matmul_params_per_token(cfg) + attn)


def gmm_flops_bytes(cfg: dict, tokens: int) -> tuple[float, float]:
    """FLOPs and bytes of the grouped matmul's products for ``tokens``
    tokens of one node through every MoE layer: gate, up and down, each
    forward and in both backward products (the rows' and the weights'
    gradients)."""
    d, f, held = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["n_routed_experts"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    pairs = tokens * held_pairs_per_token(cfg)
    flops = layers * 3 * 3 * 2 * pairs * d * f
    bytes_ = layers * 3 * 3 * 2 * (pairs * d + held * d * f + pairs * f)
    return flops, bytes_
