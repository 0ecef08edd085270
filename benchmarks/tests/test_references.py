"""Each plain reference against the program's own eager forward pass at a
tiny size on the CPU: same weights, same batch, dropout off.  They are
written apart from the program and have to agree with it to rounding."""
import numpy as np
import pytest

from benchmarks.harness import loadgen
from benchmarks.reference import bert_mlm, causal_lm, gpt2_lm


def _params(model):
    return {n: p._data for n, p in model.named_parameters()}


def test_bert_mlm_reference_matches_the_model_in_eval_mode():
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    args = dict(vocab_size=300, hidden_size=32, num_layers=2, num_heads=4,
                ffn_hidden=64, max_seq_len=32, dropout=0.1,
                scan_layers=True)
    paddle.seed(0)
    model = BertForPretraining(BertConfig(**args))
    model.eval()
    traffic = {"objective": "mlm", "batch_per_replica": 3, "seq": 16,
               "ring": 1, "zipf_a": 1.0, "mask_rate": 0.3, "mask_id": 5}
    ids, labels = loadgen.batches(traffic, 1, 300, 1)[0]
    want = float(np.asarray(model.loss(paddle.to_tensor(ids),
                                       paddle.to_tensor(labels))._data))
    got = bert_mlm.loss(_params(model), ids, labels, args)
    assert got == pytest.approx(want, rel=2e-5)


def test_gpt2_reference_matches_the_model_in_eval_mode():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    args = dict(vocab_size=300, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=16, dropout=0.1, attn_dropout=0.0,
                scan_layers=True)
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(**args))
    model.eval()
    traffic = {"objective": "causal_lm", "batch_per_replica": 3, "seq": 16,
               "ring": 1, "zipf_a": 1.0}
    ids, labels = loadgen.batches(traffic, 1, 300, 1)[0]
    want = float(np.asarray(model.loss(paddle.to_tensor(ids),
                                       paddle.to_tensor(labels))._data))
    got = gpt2_lm.loss(_params(model), ids, labels, args)
    assert got == pytest.approx(want, rel=2e-5)


def test_causal_lm_reference_matches_prefill_position_by_position():
    from paddle_tpu.generation import TinyCausalLM

    model = TinyCausalLM(vocab_size=97, num_layers=2, num_heads=4,
                         head_dim=8, mlp_ratio=2, max_positions=64, seed=3)
    tokens = np.random.default_rng(0).integers(0, 97, 23).tolist()
    got = np.asarray(causal_lm.next_token_logits(
        model.decode_params(), tokens, model.num_heads, 5))
    assert got.shape == (5, 97)
    for j in range(5):
        want, _, _ = model.prefill(np.asarray(tokens[:23 - 4 + j]))
        assert np.allclose(got[j], np.asarray(want), atol=2e-5)
