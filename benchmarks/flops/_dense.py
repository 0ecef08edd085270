"""Operations and bytes of dense transformer work, from shapes alone.
A multiply-add counts 2.  Nothing here counts an embedding gather, a
recomputed forward pass, or a collective."""


def block_matmul_flops_per_token(hidden, ffn):
    """Forward pass of one block's weight matmuls: q, k, v and output
    projections (4 h*h) and the two MLP matmuls (2 h*ffn)."""
    return 2 * (4 * hidden * hidden + 2 * hidden * ffn)


def attention_flops_per_token(hidden, seq, causal):
    """Forward QK^T and PV for one token against `seq` keys: 2 * 2 * seq *
    hidden, of which a causal mask needs half."""
    return 4 * seq * hidden * (0.5 if causal else 1.0)


def flash_call(batch, heads, seq, head_dim, causal, backward, itemsize):
    """(flops, bytes) one flash-attention kernel call needs.  Forward: two
    matmuls over the score matrix, reads q, k, v and writes o.  Backward
    (dq, dk, dv with the scores recomputed inside the kernel): five
    matmuls, reads q, k, v, o, do and writes dq, dk, dv."""
    score = batch * heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    tensor = batch * heads * seq * head_dim * itemsize
    if backward:
        return 2 * 5 * score, 8 * tensor
    return 2 * 2 * score, 4 * tensor


def paged_attention_call(heads, head_dim, kv_tokens, score_pairs, itemsize):
    """(flops, bytes) of one paged-attention call over one layer's pool:
    every live key and value is read once (`kv_tokens` of them, K and V);
    `score_pairs` is the number of (query row, visible key) pairs."""
    return (4 * heads * head_dim * score_pairs,
            2 * kv_tokens * heads * head_dim * itemsize)
