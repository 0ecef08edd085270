"""Operations of one GPT-2 large training step, and of its flash kernel
calls, as `gpt2-large` runs it."""
from benchmarks.flops import _dense


def train_step_flops(config, traffic, data_replicas):
    """Forward + backward (3x forward) of one global batch: block matmuls,
    causal attention (the half of the score matrix the mask keeps) and
    the tied vocabulary projection.  Remat's second forward pass and the
    ZeRO-3 gathers are the program's choice and count nothing."""
    m = config["builder"]["model_args"]
    h, v = m["hidden_size"], m["vocab_size"]
    s = traffic["seq"]
    per_token = (m["num_layers"] * (
        _dense.block_matmul_flops_per_token(h, 4 * h)
        + _dense.attention_flops_per_token(h, s, causal=True))
        + 2 * h * v)
    tokens = traffic["batch_per_replica"] * data_replicas * s
    return 3 * per_token * tokens


def flash_call(config, traffic, backward):
    """(flops, bytes) of one flash kernel call on one chip: the chip's
    share is one data replica's batch and 1/model of the heads.  The
    program hands the kernel float32 q, k and v even under bf16 autocast
    (the trace shows f32[B*H, S, D] operands: PR 22), so 4 bytes each."""
    m = config["builder"]["model_args"]
    heads = m["num_heads"] // config["builder"]["mesh"].get("model", 1)
    return _dense.flash_call(
        traffic["batch_per_replica"], heads, traffic["seq"],
        m["hidden_size"] // m["num_heads"], causal=True, backward=backward,
        itemsize=4)
