"""Operations and bytes of `glm-4.7-flash-d7`'s two kernels, from shapes
alone: what the algorithm needs, whatever implements it.

Latent attention in the absorbed form: a cached token's row (576
numbers, bf16) is the key of all 20 heads and, in its first 512
numbers, their value, so a call reads each live row once and spends
2 x 20 x (576 + 512) operations on a (query row, visible key) pair.
The expert layer reads the three matrices of every expert that got a
row, once, and spends 2 x 3 x hidden x width operations on a (token,
expert) pair."""
ITEMSIZE = 2    # bf16 rows and weights


def _args(config):
    return config["builder"]["model_args"]


def ragged_call(config, kv_tokens, score_pairs):
    """(flops, bytes) of one latent-attention call (one layer, one engine
    step) over sequences that hold `kv_tokens` cached rows in all."""
    m = _args(config)
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return (2 * m["num_heads"] * (row + m["kv_lora_rank"]) * score_pairs,
            kv_tokens * row * ITEMSIZE)


def moe_call(config, assignments, experts_touched):
    """(flops, bytes) of one expert layer's grouped products (gate, up
    and down) for `assignments` (token, expert) pairs over
    `experts_touched` distinct experts."""
    m = _args(config)
    matrix = m["hidden_size"] * m["moe_intermediate_size"]
    return (2 * 3 * matrix * assignments,
            3 * matrix * ITEMSIZE * experts_touched)
