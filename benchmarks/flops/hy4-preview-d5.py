"""Operations and bytes of `hy4-preview-d5`'s kernels, from shapes alone:
what the algorithm needs, whatever implements it.

Selected-token latent attention in the absorbed form: a selected token's
latent row (576 numbers, bf16: 1,152 B) is the key of all 64 heads and,
in its first 512 numbers, their value, so a (query row, selected token)
pair reads the row once and spends 2 x 64 x (576 + 512) operations.
The indexer reads each scored context's index keys (128 numbers, bf16:
256 B a token) at least once a `full` layer and spends 2 x 32 x 128
operations on a (query row, context token) pair.  The expert layer
reads the three matrices of every expert that got a row, once, and
spends 2 x 3 x hidden x width operations on a (token, expert) pair."""
ITEMSIZE = 2    # bf16 rows, keys and weights


def _args(config):
    return config["builder"]["model_args"]


def sparse_call(config, pairs):
    """(flops, bytes) of the selected-token calls over `pairs` (query
    row, selected token) pairs, summed over rows and layers."""
    m = _args(config)
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return (2 * m["num_heads"] * (row + m["kv_lora_rank"]) * pairs,
            pairs * row * ITEMSIZE)


def indexer_call(config, pairs, keys):
    """(flops, bytes) of the indexer's scoring over `pairs` (query row,
    context token) pairs whose contexts hold `keys` tokens, each context
    counted once a `full` layer."""
    m = _args(config)
    width = m["index_head_dim"]
    return 2 * m["index_n_heads"] * width * pairs, keys * width * ITEMSIZE


def moe_call(config, assignments, experts_touched):
    """(flops, bytes) of one expert layer's grouped products (gate, up
    and down) for `assignments` (token, expert) pairs over
    `experts_touched` distinct experts."""
    m = _args(config)
    matrix = m["hidden_size"] * m["moe_intermediate_size"]
    return (2 * 3 * matrix * assignments,
            3 * matrix * ITEMSIZE * experts_touched)
