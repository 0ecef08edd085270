"""`hy4-preview-d5.longdoc-mixed-closed` and
`granite-4.0-h-small-d10.shortchat-bursty` as data of the harness: the
configuration is the published one cut by depth, by the chip's share of
the experts and by the vocabulary, the serving cell rehearses on the CPU
through its runner and is `correct`, the new readers read what they say
they read, the flops are what a hand computes, and the reference in
float8 is not correct under the runner's own verdict."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

ROOT = manifest.ROOT
CELL = "hy4-preview-d5.longdoc-mixed-closed"
BURSTY = "granite-4.0-h-small-d10.shortchat-bursty"
CLOSED = "granite-4.0-h-small-d10.chat-closed"


@pytest.fixture(scope="module")
def cells():
    m = manifest.load(ROOT)
    return {name: manifest.Cell(m, name, ROOT)
            for name in (CELL, BURSTY, CLOSED)}


# Hy4-preview's published config.json: every key the configuration keeps,
# and the published values of the keys it cuts (the first five of each
# per-layer list)
SOURCE = "https://huggingface.co/tencent/Hy4-preview/blob/main/config.json"
PUBLISHED = {
    "attention_bias": False, "bitwise_backward_align": False,
    "enable_ihc": True, "enable_lm_head_fp32": True, "gated_mla": True,
    "gating_type": "elementwise", "hc_eps": 1e-06, "hc_magnitude": 2,
    "hc_mult": 4, "head_dim": 64, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "learnable_sink": True, "learnable_sink_init": 0,
    "max_position_embeddings": 1048576, "model_type": "hy_v4",
    "moe_intermediate_size": 2048, "mtp_loss_factor": 0.1, "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 10000000, "rope_type": "default"},
    "routed_scaling_factor": 2.827, "swiglu_limit": 10,
    "tie_word_embeddings": False, "topk_group": 1, "use_dsa": True,
    "use_mla": True, "v_head_dim": 256}
CUT = {"num_hidden_layers": 78, "n_routed_experts": 256, "vocab_size": 120832,
       "layer_types": ["deepseek_sparse_attention"] * 5,
       "mlp_layer_types": ["dense"] + ["sparse"] * 4,
       "indexer_types": ["full", "full", "shared", "shared", "shared"]}


def test_the_configuration_is_the_published_one_cut(cells):
    config = cells[CELL].config
    assert config["source"] == SOURCE
    entry = next(c for c in cells[CELL].manifest["configs"]
                 if c["name"] == "hy4-preview-d5")
    assert sorted(entry["reduced"]) == sorted(CUT)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "indexer_types"):
        assert config[key] == CUT[key]
    assert (config["n_routed_experts"], config["vocab_size"]) == (
        16, CUT["vocab_size"] // 8)
    args = config["builder"]["model_args"]
    for key in set(args) & set(config):
        assert args[key] == config[key], key
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads")):
        assert args[ours] == config[theirs]
    assert args["router_width"] == CUT["n_routed_experts"]
    assert args["experts_held"] == [0, 16]
    assert args["rope_theta"] == PUBLISHED["rope_parameters"]["rope_theta"]


def test_the_traffic_is_long_shared_documents_prefilled_beside_decoding(
        cells):
    traffic = cells[CELL].traffic
    assert (traffic["loop"], traffic["clients"]) == ("closed", 40)
    assert cells[CELL].config["builder"]["engine"][
        "max_decode_slots"] == 32
    assert traffic["prefix"] == {"count": 3, "tokens": 65536,
                                 "zipf_a": 0.5, "block": 24}
    assert (traffic["prompt_tokens"]["lo"],
            traffic["prompt_tokens"]["hi"]) == (128, 8192)
    assert (traffic["output_tokens"]["lo"],
            traffic["output_tokens"]["hi"]) == (64, 256)


def test_the_bursty_cell_keeps_chat_closed_s_lengths(cells):
    bursty, closed = cells[BURSTY].traffic, cells[CLOSED].traffic
    assert bursty["loop"] == "open" and "prefix" not in bursty
    assert bursty["arrivals"]["process"] == "gamma"
    assert bursty["arrivals"]["cv"] == 3
    for key in ("prompt_tokens", "output_tokens"):
        assert bursty[key] == closed[key]
    assert (bursty["ramp_s"], bursty["timeout_ms"]) == (12.5, 60000)
    assert [m["name"] for m in cells[BURSTY].end_to_end] == [
        "serve_gap_ms_p95", "setup_s"]


def _obs(cells, **over):
    cell = cells[CELL]
    obs = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
           "result": {"window_s": 50.0, "counters": {
               "generation.dsa_tokens_attended": 2048 * 5 * 1000,
               "generation.dsa_context_tokens": 70000 * 5 * 1000,
               "generation.dsa_index_keys": 70000 * 2 * 40}},
           "trace": None, "clock": {}, "device": {"platform": "tpu"},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    obs.update(over)
    return obs


def test_the_selected_share_is_the_counters_ratio(cells):
    read = cells[CELL].module("layer_metrics",
                              "attention.selected_share").read
    assert read(_obs(cells)) == pytest.approx(100 * 2048 / 70000)
    assert read(_obs(cells, result={"counters": {}})) is None


def test_the_device_readers_read_nothing_without_a_trace(cells):
    for name in ("indexer.time_share", "kernel.indexer_roofline",
                 "kernel.sparse_latent.time_share",
                 "kernel.sparse_latent_roofline", "step.hyper.time_share"):
        assert cells[CELL].module("layer_metrics", name).read(
            _obs(cells)) is None, name


def test_the_flops_are_what_a_hand_computes(cells):
    flops = cells[CELL].flops()
    config = cells[CELL].config
    assert flops.sparse_call(config, 10) == (2 * 64 * (576 + 512) * 10,
                                             10 * 576 * 2)
    assert flops.indexer_call(config, 10, 7) == (2 * 32 * 128 * 10,
                                                 7 * 128 * 2)
    assert flops.moe_call(config, 3, 2) == (2 * 3 * 6144 * 2048 * 3,
                                            3 * 6144 * 2048 * 2 * 2)


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)


def test_the_serving_cell_rehearses_on_the_cpu_and_is_correct():
    done = _run("benchmarks/run.py", "--workload", CELL, "--seed",
                "2147484141", "--seconds", "4", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20 and line["rehearsal"] is True
    metrics = line["metrics"]
    # counts only on the CPU: no time under a metric's name
    assert set(metrics) == {
        "engine.row_utilization", "engine.prefix_hit_rate",
        "step.compiles_in_window", "moe.load_max_over_mean",
        "attention.selected_share"}
    # the preset's 16 of contexts up to 94 tokens
    assert 10 < metrics["attention.selected_share"]["value"] < 60
    assert line["checks"]["agreeing_share"]["value"] == 1.0


def test_the_control_in_a_lower_precision_is_not_correct():
    done = _run("tools/precision_control.py", "--workload", CELL, "--seed",
                "2147484151", "--rehearse")
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-2000:])
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["control"] == "float8_e4m3fn" and line["correct"] is False


def test_a_control_that_leaves_the_document_out_counts_it_as_agreeing():
    done = _run("tools/precision_control.py", "--workload", CELL, "--seed",
                "2147484151", "--rehearse", "--max-tokens", "41")
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-2000:])
    line = json.loads(done.stdout.strip().splitlines()[-1])
    *read, left = line["requests"]
    assert [len(r["short"]) for r in read] == [4, 4]
    assert all("router_margin" in r for r in read)
    assert "left out" in left["what"] and left["short"] == [0.0] * 4
    assert line["correct"] is False
