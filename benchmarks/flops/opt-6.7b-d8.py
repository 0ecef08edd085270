"""Operations and bytes of the ragged paged-attention kernel as
`opt-6.7b-d8` runs it: float32 pools, every head on the one chip."""
from benchmarks.flops import _dense


def ragged_call(config, kv_tokens, score_pairs):
    """(flops, bytes) of one kernel call (one layer, one engine step) that
    serves sequences holding `kv_tokens` keys in all."""
    m = config["builder"]["model_args"]
    return _dense.paged_attention_call(
        m["num_heads"], m["head_dim"], kv_tokens, score_pairs, itemsize=4)
