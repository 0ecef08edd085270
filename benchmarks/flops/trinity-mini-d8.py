"""Operations and bytes of `trinity-mini-d8`'s two kernels, from shapes
alone: what the algorithm needs, whatever implements it.

Grouped-query attention over a row cache: a cached token of a layer is K
and V of 4 heads of 128 in bf16, 2,048 B.  One step's attention has to
read each live sequence's rows once a layer: all of them in a full
layer, at most the last 2,048 (with the step's own rows) in a window
layer; and it spends 2 x 2 x 32 x 128 operations on a (query row,
visible key) pair a layer (a score and a value product, 32 query heads
of 128).  The expert layer reads the three matrices of every expert that
got a row, once, and spends 2 x 3 x hidden x width operations on a
(token, expert) pair."""
ITEMSIZE = 2    # bf16 rows and weights


def _args(config):
    return config["builder"]["model_args"]


def _layers(config):
    kinds = _args(config)["layer_types"]
    window = sum(1 for kind in kinds if kind.startswith(("sliding", "window")))
    return len(kinds) - window, window


def ragged_call(config, kv_tokens_full, kv_tokens_window, score_pairs_full,
                score_pairs_window):
    """(flops, bytes) of ONE STEP's attention, all layers: over sequences
    whose full layers hold `kv_tokens_full` rows in all and whose window
    layers have to read `kv_tokens_window` (a sequence's rows inside its
    window), with `score_pairs_full` / `score_pairs_window` (query row,
    visible key) pairs a layer of each kind."""
    m = _args(config)
    full, window = _layers(config)
    row = 2 * m["num_kv_heads"] * m["head_dim"]
    pair = 2 * 2 * m["num_heads"] * m["head_dim"]
    return (pair * (full * score_pairs_full + window * score_pairs_window),
            row * ITEMSIZE * (full * kv_tokens_full
                              + window * kv_tokens_window))


def moe_call(config, assignments, experts_touched):
    """(flops, bytes) of one expert layer's grouped products (gate, up
    and down) for `assignments` (token, expert) pairs over
    `experts_touched` distinct experts."""
    m = _args(config)
    matrix = m["hidden_size"] * m["moe_intermediate_size"]
    return (2 * 3 * matrix * assignments,
            3 * matrix * ITEMSIZE * experts_touched)
