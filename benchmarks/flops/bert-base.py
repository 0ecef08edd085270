"""Operations of one BERT pretraining step as `bert-base` runs it."""
from benchmarks.flops import _dense


def train_step_flops(config, traffic, data_replicas):
    """Forward + backward (3x forward) of one global batch: the encoder's
    matmuls and full attention, the MLM transform and the tied vocabulary
    projection at every position.  The pooler and the NSP head feed no
    loss here and count nothing."""
    m = config["builder"]["model_args"]
    h, f, v = m["hidden_size"], m["ffn_hidden"], m["vocab_size"]
    s = traffic["seq"]
    per_token = (m["num_layers"] * (
        _dense.block_matmul_flops_per_token(h, f)
        + _dense.attention_flops_per_token(h, s, causal=False))
        + 2 * h * h + 2 * h * v)
    tokens = traffic["batch_per_replica"] * data_replicas * s
    return 3 * per_token * tokens
