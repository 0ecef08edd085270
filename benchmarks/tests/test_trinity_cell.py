"""`trinity-mini-d8.mixed-closed` and `opt-6.7b-d8.chat-bursty` as data of
the harness: their files load, the serving cell's rehearsal runs on the
CPU through its runner and is `correct`, the three new readers read what
they say they read, `flops.ragged_call` is what a hand computes, and the
reference in float8 is not correct under the runner's own verdict."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import loadgen, manifest

ROOT = manifest.ROOT
CELL = "trinity-mini-d8.mixed-closed"
BURSTY = "opt-6.7b-d8.chat-bursty"
STEADY = "opt-6.7b-d8.chat-steady"


@pytest.fixture(scope="module")
def cells():
    m = manifest.load(ROOT)
    return {name: manifest.Cell(m, name, ROOT)
            for name in (CELL, BURSTY, STEADY)}


def test_the_configuration_is_the_published_one_cut_by_depth(cells):
    config = cells[CELL].config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "num_dense_layers": 2, "route_scale": 2.826, "sliding_window": 2048,
        "vocab_size": 200192, "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "global_attn_every_n_layers": 4, "max_position_embeddings": 131072,
        "mup_enabled": True, "n_group": 1, "topk_group": 1,
        "rope_scaling": None, "model_type": "afmoe"}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    args = config["builder"]["model_args"]
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("n_routed_experts", "num_experts"),
                         ("n_shared_experts", "num_shared_experts"),
                         ("first_k_dense_replace", "num_dense_layers"),
                         ("routed_scaling_factor", "route_scale")):
        assert args[ours] == config[theirs], ours
    for key in set(args) & set(config):
        assert args[key] == config[key], key
    entry = next(c for c in cells[CELL].manifest["configs"]
                 if c["name"] == "trinity-mini-d8")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert {"deployment", "departures", "assumed", "check",
            "reduced_why"} <= set(config)
    assert config["builder"]["expect"]["prefix_cache"] is False


def test_the_traffic_mixes_contexts_inside_and_past_the_window(cells):
    traffic = cells[CELL].traffic
    assert (traffic["loop"], traffic["clients"], traffic.get("prefix")) == (
        "closed", 24, None)
    requests = loadgen.schedule(dict(traffic, pool=64), 2147483901, 200192,
                                0.0)
    own = np.asarray([len(r.prompt) for r in requests])
    assert own.min() >= 256 and own.max() <= 32768
    for lo in range(0, 64, 16):         # one from each stratum a block
        block = np.sort(own[lo:lo + 16])
        assert (block[:6] <= 2048).all() and (block[7:] > 2048).all()
    out = [r.max_new_tokens for r in requests[24:]]
    assert min(out) >= 64 and max(out) <= 512


def test_chat_bursty_is_chat_steady_but_for_its_arrivals(cells):
    bursty, steady = cells[BURSTY].traffic, cells[STEADY].traffic
    same = set(steady) - {"arrivals", "schedule_seed", "why", "notes"}
    assert {k: bursty[k] for k in same} == {k: steady[k] for k in same}
    assert bursty["arrivals"] == {"process": "gamma", "rate_per_s": 3.2,
                                  "cv": 3}
    assert cells[BURSTY].config is not None and \
        cells[BURSTY].config == cells[STEADY].config
    # the realisation the file's notes describe, whatever the run's seed
    counts = set()
    for seed in (1, 2147483999):
        due = np.asarray([r.due_s for r in loadgen.schedule(
            bursty, seed, 50272, 67.5)])
        window = due[(due >= 12.5) & (due < 62.5)]
        counts.add((int((due < 12.5).sum()), len(window), max(
            int(((window >= a) & (window < a + 2)).sum()) for a in window)))
    assert counts == {(44, 167, 35)}
    names = {m["name"] for m in cells[BURSTY].end_to_end}
    assert names == {"serve_gap_ms_p95", "setup_s"}
    assert {m["name"] for m in cells[BURSTY].per_layer} == {
        m["name"] for m in cells[STEADY].per_layer}


def test_the_runner_the_reference_and_the_flops_load(cells):
    cell = cells[CELL]
    runner = cell.module("runners", cell.config["runner"])
    assert callable(runner.run) and callable(runner.verdict)
    assert runner.check_lengths(cell.config["check"], cell.traffic) == [
        40, 3000, 20000]
    assert callable(cell.module(
        "reference", cell.config["reference"]).next_token_logits)
    flops = cell.flops()
    # by hand: 2 full and 6 window layers, 2,048 B a token and layer,
    # 2 x 2 x 32 x 128 operations a pair
    ops, nbytes = flops.ragged_call(cell.config, 1000, 1000, 1000, 1000)
    assert nbytes == 8 * 1000 * 2048 and ops == 8 * 1000 * 16384
    ops, nbytes = flops.ragged_call(cell.config, 112000, 32768, 112000,
                                    32768)
    assert nbytes == (2 * 112000 + 6 * 32768) * 2048
    assert ops == (2 * 112000 + 6 * 32768) * 2 * 2 * 32 * 128
    ops, nbytes = flops.moe_call(cell.config, 4224, 128)
    assert nbytes == 128 * 3 * 2048 * 1024 * 2
    assert ops == 4224 * 3 * 2 * 2048 * 1024
    # what the glm cell's readers read of this configuration
    assert cell.config["builder"]["model_args"]["n_routed_experts"] == 128


def test_the_released_share_reads_the_counters_window_delta(cells):
    read = cells[CELL].module("layer_metrics",
                              "cache.window_released_share").read
    assert read({"result": {"counters": {
        "generation.kv_window_pages_reserved": 400,
        "generation.kv_window_pages_released": 300}}}) == pytest.approx(75.0)
    # no context passed the window: the bypass reads 0, not nothing
    assert read({"result": {"counters": {
        "generation.kv_window_pages_reserved": 40}}}) == 0.0
    # a program without a window group (the parent's) reads nothing
    assert read({"result": {"counters": {}}}) is None


WINDOW_CALL = ("%window_attention.9 = bf16[33,4,128,128]{3,2,1,0} custom-call("
               "s32[] %bitcast.2, s32[17408]{0} %copy-done.61, s32[196]{0} "
               "%copy-done.120, s32[1]{0} %dynamic_slice.2), "
               "custom_call_target=\"tpu_custom_call\"")
FULL_CALL = WINDOW_CALL.replace("%window_attention.9", "%full_attention.3"
                                ).replace("s32[196]", "s32[3136]")
GROUPED_CALL = ("%ragged-dot-none.3 = f32[4224,2048]{1,0} custom-call(s32[1]{0}"
                " %get-tuple-element.44, s32[129]{0} %get-tuple-element.45), "
                "custom_call_target=\"tpu_custom_call\"")


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
def profile(monkeypatch, tmp_path):
    """A profile of two steps: 3 window calls of 1 ms and a full call of
    2 ms a step between grouped products of 1 ms; the window cuts the
    last full call in half."""
    import jax

    from benchmarks.trace import custom_calls, reduce

    ms = 1_000_000
    ops, at = [], 0
    for _ in range(2):
        for call, n in ((WINDOW_CALL, 1), (GROUPED_CALL, 1), (WINDOW_CALL, 1),
                        (WINDOW_CALL, 1), (FULL_CALL, 2)):
            ops.append(_Event(call, at, n * ms))
            at += n * ms
    planes = [
        _Plane("/host:CPU", [_Line("python", [
            _Event(reduce.WINDOW_SPAN, 0, 11 * ms)])]),
        _Plane(reduce.DEVICE_PLANE + "0", [_Line(reduce.OPS_LINE, ops)])]
    data = types.SimpleNamespace(planes=planes)
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    monkeypatch.setattr(reduce, "find_xplane", lambda trace_dir: str(tmp_path))
    custom_calls._custom_calls.cache_clear()
    yield
    custom_calls._custom_calls.cache_clear()


def _obs(cells, **more):
    return dict({"cell": cells[CELL], "config": cells[CELL].config,
                 "trace": {"busy_s": 0.010, "window_s": 0.011, "spans": {
                     "generation::dispatch": [0.001, 0.001]}},
                 "peaks": {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
                 "result": {}, "clock": {"traced": (0.0, 0.011)}}, **more)


def test_the_rule_tells_the_attention_kernel_from_the_grouped_products():
    from benchmarks.trace import attention_calls, custom_calls

    assert [attention_calls.is_attention(c) for c in (
        WINDOW_CALL, FULL_CALL, GROUPED_CALL,
        "%fusion.7 = f32[80,2048]{1,0} fusion(f32[80,2048]{1,0} %p)")] == [
            True, True, False, False]
    assert custom_calls.is_grouped(GROUPED_CALL)


def test_the_time_share_reads_the_attention_calls_inside_the_window(
        cells, profile):
    share = cells[CELL].module("layer_metrics", "kernel.gqa.time_share")
    grouped = cells[CELL].module("layer_metrics", "moe.time_share")
    # 3 + 2 and 3 + 1 of the 10 busy milliseconds; the products' 2
    assert share.read(_obs(cells)) == pytest.approx(90.0)
    assert grouped.read(_obs(cells)) == pytest.approx(20.0)
    assert share.read(_obs(cells, trace=None)) is None


def test_the_roofline_is_a_step_s_least_time_over_the_kernel_s_time_a_step(
        cells, profile):
    read = cells[CELL].module("layer_metrics", "kernel.gqa_roofline").read
    # two sequences decoding over the whole trace: 1,000 tokens (inside
    # the window) and 10,000 (past it)
    tracked = [types.SimpleNamespace(
        token_s=[-1.0], done_abs=None,
        request=types.SimpleNamespace(prompt=[0] * n)) for n in (999, 9999)]
    got = read(_obs(cells, result={"tracked": tracked}))
    least = (2 * 11000 + 6 * (1000 + 2048)) * 2048 / 819e9
    assert got == pytest.approx(100 * least / (0.009 / 2))
    # splitting every call in two changes nothing: steps divide, not calls
    assert read(_obs(cells, result={"tracked": tracked}, peaks=None)) is None
    spans = {"busy_s": 0.010, "window_s": 0.011, "spans": {}}
    assert read(_obs(cells, result={"tracked": tracked}, trace=spans)) is None


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)


def test_the_serving_cell_rehearses_on_the_cpu_and_is_correct():
    done = _run("benchmarks/run.py", "--workload", CELL, "--seed",
                "2147483951", "--seconds", "4", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20 and line["rehearsal"] is True
    metrics = line["metrics"]
    # counts only on the CPU: no time under a metric's name
    assert set(metrics) == {
        "engine.row_utilization", "step.compiles_in_window",
        "kernel.ragged.grid_utilization", "moe.load_max_over_mean",
        "cache.window_released_share"}
    assert metrics["step.compiles_in_window"]["value"] == 0
    assert 30 < metrics["cache.window_released_share"]["value"] < 95
    assert 0 < metrics["kernel.ragged.grid_utilization"]["value"] <= 100
    checks = line["checks"]
    assert checks["agreeing_share"]["value"] == 1.0
    assert checks["window_pages_held_by_finished_requests"]["value"] == 0
    assert checks["most_window_pages_a_request_held"]["value"] <= \
        checks["most_window_pages_a_request_held"]["limit"]
    assert checks["window_pages_released_in_the_check"]["value"] >= \
        checks["window_pages_released_in_the_check"]["limit"] > 0


def test_the_reference_in_float8_is_not_correct():
    """The control rounds every matrix of the tiny preset to float8 e4m3
    and has to fail the verdict the runner holds the engine to."""
    done = _run("tools/precision_control.py", "--workload", CELL, "--seed",
                "2147483951", "--rehearse")
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-2000:])
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and len(line["requests"]) == 3
