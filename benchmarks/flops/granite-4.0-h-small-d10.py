"""Operations and bytes of `granite-4.0-h-small-d10`'s kernels, from
shapes alone: what the algorithm needs, whatever implements it.

Grouped-query attention over a row cache, in the ONE attention layer of
the ten: a cached token is K and V of 8 heads of 128 in bf16, 4,096 B.
One step's attention has to read each live sequence's rows once, and it
spends 2 x 2 x 32 x 128 operations on a (query row, visible key) pair
(a score and a value product, 32 query heads of 128).  No layer keeps a
window.  The expert layer reads the three matrices of every HELD expert
that got a row, once, and spends 2 x 3 x hidden x width operations on a
(token, expert) pair computed here.

A state-space layer's one-token update has to read and to write a
slot's recurrent state (128 heads x 64 x 128 in float32, 4,194,304 B)
and its convolution tail (3 rows of 8,448 lanes in bf16, 50,688 B) for
every row updated, whatever the context's length.  Its chunked scan
spends, a token and layer, the state products of the recurrence
(2 x 2 x d_inner x d_state: into the state and out of it) and, inside a
block of `mamba_chunk_size` rows, a score against each earlier row of
the block (2 x d_state) and a weighted sum of those rows' inputs
(2 x d_inner), half a block's rows on average."""
ITEMSIZE = 2    # bf16 rows, weights and tails
STATE_ITEMSIZE = 4


def _args(config):
    return config["builder"]["model_args"]


def _attention_layers(config):
    return sum(1 for kind in _args(config)["layer_types"]
               if kind == "attention")


def ragged_call(config, kv_tokens_full, kv_tokens_window, score_pairs_full,
                score_pairs_window):
    """(flops, bytes) of ONE STEP's attention, all attention layers,
    over sequences that hold `kv_tokens_full` rows in all, with
    `score_pairs_full` (query row, visible key) pairs a layer.  The
    window arguments are the readers' (`kernel.gqa_roofline`): no layer
    has a window, so they count nothing."""
    del kv_tokens_window, score_pairs_window
    m = _args(config)
    layers = _attention_layers(config)
    row = 2 * m["num_kv_heads"] * m["head_dim"]
    pair = 2 * 2 * m["num_heads"] * m["head_dim"]
    return (pair * layers * score_pairs_full,
            row * ITEMSIZE * layers * kv_tokens_full)


def moe_call(config, assignments, experts_touched):
    """(flops, bytes) of one expert layer's grouped products (gate, up
    and down) for `assignments` (token, expert) pairs computed here over
    `experts_touched` distinct held experts."""
    m = _args(config)
    matrix = m["hidden_size"] * m["moe_intermediate_size"]
    return (2 * 3 * matrix * assignments,
            3 * matrix * ITEMSIZE * experts_touched)


def ssm_update_bytes(config, rows):
    """Bytes the one-token update cannot avoid for `rows` (row, state
    layer) pairs (`generation.ssm_rows_updated`): the state and the tail
    read and written once each."""
    m = _args(config)
    d_inner = m["mamba_n_heads"] * m["mamba_d_head"]
    state = d_inner * m["mamba_d_state"] * STATE_ITEMSIZE
    tail = ((m["mamba_d_conv"] - 1) * (d_inner + 2 * m["mamba_d_state"])
            * ITEMSIZE)
    return rows * 2 * (state + tail)


def ssm_scan_flops(config, tokens):
    """Operations of the chunked scan for `tokens` (token, state layer)
    pairs (`generation.ssm_tokens_scanned`)."""
    m = _args(config)
    d_inner = m["mamba_n_heads"] * m["mamba_d_head"]
    inside = (m["mamba_chunk_size"] / 2) * 2 * (m["mamba_d_state"] + d_inner)
    return tokens * (2 * 2 * d_inner * m["mamba_d_state"] + inside)
