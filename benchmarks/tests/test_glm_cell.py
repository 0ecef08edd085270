"""`glm-4.7-flash-d7.docqa-closed` and `bert-base.pretrain-s512` as data
of the harness: their files load, the serving cell's rehearsal runs on
the CPU through the new runner, and the two expert-layer readers read
what they say they read."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loadgen, manifest

ROOT = manifest.ROOT
GLM = "glm-4.7-flash-d7.docqa-closed"
BERT = "bert-base.pretrain-s512"


@pytest.fixture(scope="module")
def cells():
    m = manifest.load(ROOT)
    return {name: manifest.Cell(m, name, ROOT) for name in (GLM, BERT)}


def test_the_configuration_is_the_published_one_cut_by_depth(cells):
    config = cells[GLM].config
    published = {
        "hidden_size": 2048, "num_attention_heads": 20, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "vocab_size": 154880,
        "first_k_dense_replace": 1, "rope_theta": 1000000,
        "torch_dtype": "bfloat16"}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 7
    args = config["builder"]["model_args"]
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads")):
        assert args[ours] == config[theirs]
    for key in set(args) & set(config):
        assert args[key] == config[key], key
    entry = next(c for c in cells[GLM].manifest["configs"]
                 if c["name"] == "glm-4.7-flash-d7")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert {"deployment", "departures", "assumed", "check"} <= set(config)


def test_the_traffic_asks_every_document_in_every_block(cells):
    traffic = cells[GLM].traffic
    requests = loadgen.schedule(dict(traffic, pool=96, prefix=dict(
        traffic["prefix"], tokens=8)), 2147483901, 154880, 0.0)
    for lo in range(0, 96, 24):
        assert {r.prefix_id for r in requests[lo:lo + 24]} == set(range(6))
    own = [len(r.prompt) - 8 for r in requests]
    assert min(own) >= 32 and max(own) <= 128
    assert cells[BERT].traffic["seq"] * cells[BERT].traffic[
        "batch_per_replica"] == 8192


def test_the_runner_the_reference_and_the_flops_load(cells):
    cell = cells[GLM]
    assert callable(cell.module("runners", cell.config["runner"]).run)
    assert callable(cell.module(
        "reference", cell.config["reference"]).next_token_logits)
    flops = cell.flops()
    ops, nbytes = flops.ragged_call(cell.config, 1000, 1000)
    assert nbytes == 1000 * 576 * 2 and ops == 2 * 20 * (576 + 512) * 1000
    ops, nbytes = flops.moe_call(cell.config, 64, 40)
    assert nbytes == 40 * 3 * 2048 * 1536 * 2
    assert ops == 64 * 3 * 2 * 2048 * 1536


def test_the_load_reader_reads_the_counters_window_delta(cells):
    read = cells[GLM].module("layer_metrics", "moe.load_max_over_mean").read
    obs = {"config": cells[GLM].config, "result": {"counters": {
        "generation.moe_assignments_total": 6400,
        "generation.moe_assignments_max_expert": 250}}}
    assert read(obs) == pytest.approx(2.5)
    # a program without the counters (the parent's) reads nothing
    assert read({"config": cells[GLM].config,
                 "result": {"counters": {}}}) is None


# as a chip trace of the cell shows them (PR 28, call 8), layouts cut
GROUPED_CALL = ("%ragged-dot-none.3 = f32[320,3072]{1,0} custom-call(s32[1]{0}"
                " %get-tuple-element.44, s32[65]{0} %get-tuple-element.45), "
                "custom_call_target=\"tpu_custom_call\"")
METADATA_CALL = ("%ragged-dot-metadata.1 = (s32[65]{0}, s32[68]{0}, s32[68]{0},"
                 " s32[1]{0}) custom-call(s32[64]{0} %get-tuple-element.142), "
                 "custom_call_target=\"tpu_custom_call\"")
LATENT_CALL = ("%latent_attention.7 = bf16[10,160,512]{2,1,0} custom-call(s32[]"
               " %bitcast.3, s32[13312]{0} %copy-done.78, s32[13312]{0} "
               "%copy-done.72, s32[1]{0} %dynamic_slice.1), "
               "custom_call_target=\"tpu_custom_call\"")
FLASH_CALL = ("%checkpoint.3 = f32[8,16,1024,64]{3,2,1,0} custom-call(f32[8,16,"
              "1024,64]{3,2,1,0} %q, f32[8] %k, f32[8] %v, s32[8] %m), "
              "custom_call_target=\"tpu_custom_call\"")


def test_the_rule_tells_the_latent_kernel_from_the_grouped_products():
    from benchmarks.trace import custom_calls, kernels

    # the reduced trace cannot: both begin with an s32 operand
    assert {kernels.short_name(c) for c in (
        GROUPED_CALL, METADATA_CALL, LATENT_CALL)} == {kernels.RAGGED}
    by_rule = {rule.__name__: [rule(c) for c in (
        GROUPED_CALL, METADATA_CALL, LATENT_CALL, FLASH_CALL,
        "%fusion.7 = f32[80,2048]{1,0} fusion(f32[80,2048]{1,0} %p)")]
        for rule in (custom_calls.is_grouped, custom_calls.is_latent)}
    assert by_rule == {
        "is_grouped": [True, True, False, False, False],
        "is_latent": [False, False, True, False, False]}


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (name, start_ns,
                                                      duration_ns)
        self.stats = ()


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


@pytest.fixture
def profile(cells, monkeypatch, tmp_path):
    """A profile of two steps: 3 latent calls of 2 ms, 2 grouped
    products of 1 ms with their metadata, a window that cuts the last
    latent call in half."""
    import jax

    from benchmarks.trace import custom_calls, reduce

    ms = 1_000_000
    ops = [_Event(LATENT_CALL, 0, 2 * ms), _Event(METADATA_CALL, 2 * ms, 0),
           _Event(GROUPED_CALL, 3 * ms, ms), _Event(LATENT_CALL, 5 * ms, 2 * ms),
           _Event(GROUPED_CALL, 8 * ms, ms), _Event(LATENT_CALL, 9 * ms, 2 * ms),
           _Event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)", 11 * ms, ms)]
    planes = [
        _Plane("/host:CPU", [_Line("python", [
            _Event(reduce.WINDOW_SPAN, 0, 10 * ms)])]),
        _Plane(reduce.DEVICE_PLANE + "0", [_Line(reduce.OPS_LINE, ops)])]

    class _Data:
        pass

    data = _Data()
    data.planes = planes
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    monkeypatch.setattr(reduce, "find_xplane", lambda trace_dir: str(tmp_path))
    custom_calls._custom_calls.cache_clear()
    yield
    custom_calls._custom_calls.cache_clear()


def _obs(cells, **more):
    return dict({"cell": cells[GLM], "config": cells[GLM].config,
                 "trace": {"busy_s": 0.008, "window_s": 0.010, "spans": {}},
                 "peaks": {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
                 "result": {}, "clock": {"traced": (0.0, 0.010)}}, **more)


def test_the_time_shares_read_their_own_calls_inside_the_window(
        cells, profile):
    latent = cells[GLM].module("layer_metrics", "kernel.latent.time_share")
    grouped = cells[GLM].module("layer_metrics", "moe.time_share")
    # 2 + 2 + 1 of the 8 busy milliseconds; 1 + 1 (the metadata's 0 adds 0)
    assert latent.read(_obs(cells)) == pytest.approx(62.5)
    assert grouped.read(_obs(cells)) == pytest.approx(25.0)
    assert latent.read(_obs(cells, trace=None)) is None
    assert grouped.read(_obs(cells, trace=None)) is None


def test_the_latent_roofline_divides_by_the_latent_calls_alone(
        cells, profile):
    import types

    read = cells[GLM].module("layer_metrics", "kernel.latent_roofline").read
    # one sequence of 1,000 prompt tokens, decoding over the whole trace
    tracked = [types.SimpleNamespace(
        token_s=[-1.0], done_abs=None,
        request=types.SimpleNamespace(prompt=[0] * 1000))]
    got = read(_obs(cells, result={"tracked": tracked}))
    # 1,001 rows of 576 bf16 numbers at 819 GB/s, over (2 + 2 + 1) / 3 ms
    assert got == pytest.approx(
        100 * (1001 * 576 * 2 / 819e9) / (0.005 / 3))
    assert read(_obs(cells, result={"tracked": tracked}, peaks=None)) is None


def test_the_grouped_roofline_reads_counters_and_calls(cells, profile):
    read = cells[GLM].module("layer_metrics", "moe.ragged_dot_roofline").read
    counters = {"generation.moe_experts_touched": 2 * 6 * 40,
                "generation.moe_assignments_total": 2 * 6 * 64}
    got = read(_obs(cells, result={"counters": counters, "window_s": 0.010}))
    # 480 experts' three matrices at 819 GB/s against the 2 ms of products
    assert got == pytest.approx(
        100 * (480 * 3 * 2048 * 1536 * 2 / 819e9) / 0.002)
    # a program without the counters (the parent's) reads nothing
    assert read(_obs(cells, result={"counters": {}, "window_s": 1.0})) is None


def test_the_verdict_holds_the_pooled_share_and_every_request(cells):
    verdict = cells[GLM].module("runners", "serve_model").verdict
    check = {"logit_margin": 0.05, "min_agreeing_share": 0.5,
             "min_agreeing_tokens_a_request": 1}

    def request(*short):
        return {"what": "r", "short": list(short),
                "router_margin": [0.01] * len(short)}

    sound = [request(0, 0, 0.4, 0), request(0, 0.02, 0, 0),
             request(0, 1.7, 0, 0.3), request(0, 0, 0, 0)]
    ok, worst, lines = verdict(check, sound, True)
    assert ok and worst == 1.7 and len(lines) == 5
    # the second asking did not come from the cache
    assert not verdict(check, sound, False)[0]
    # one request wholly wrong, the pooled share still 0.75
    assert not verdict(check, sound[:3] + [request(0.9, 1.2, 0.4, 2.0)],
                       True)[0]
    # every request agrees somewhere, too few tokens in all
    assert not verdict(check, [request(0, 1, 1, 1)] * 4, True)[0]


def test_the_precision_control_is_not_correct_on_the_tiny_preset():
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "precision_control.py"),
         "--workload", GLM, "--seed", "2147483909", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] == "float8_e4m3fn"
    assert len(line["requests"]) == 3


def test_the_serving_cell_rehearses_on_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", GLM, "--seed", "2147483907", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    metrics = line["metrics"]
    assert 1.0 <= metrics["moe.load_max_over_mean"]["value"] <= 8.0
    assert metrics["engine.prefix_hit_rate"]["value"] > 50
    assert "moe.time_share" not in metrics     # a time: never from the CPU
