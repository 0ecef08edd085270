"""`granite-4.0-h-small-d10.chat-closed` and `opt-6.7b-d8.long-shared` as
data of the harness: their files load, the configuration is the
published one cut by depth and by the chip's share of the experts, the
serving cell's rehearsal runs on the CPU through its runner and is
`correct`, the two new readers read what they say they read of a
profile's instructions, the flops are what a hand computes, and the
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
CELL = "granite-4.0-h-small-d10.chat-closed"
SHARED = "opt-6.7b-d8.long-shared"
DECODE = "opt-6.7b-d8.decode-closed"


@pytest.fixture(scope="module")
def cells():
    m = manifest.load(ROOT)
    return {name: manifest.Cell(m, name, ROOT)
            for name in (CELL, SHARED, DECODE)}


def test_the_configuration_is_the_published_one_cut_by_depth_and_share(cells):
    config = cells[CELL].config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert config["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "layer_types", "num_local_experts"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["layer_types"].count("attention") == 1
    assert (config["num_local_experts"], config["router_width"]) == (36, 72)
    args = config["builder"]["model_args"]
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("moe_intermediate_size", "intermediate_size"),
                         ("n_routed_experts", "num_local_experts"),
                         ("router_width", "router_width"),
                         ("sliding_window", "max_position_embeddings")):
        assert args[ours] == config[theirs], ours
    for key in set(args) & set(config):
        assert args[key] == config[key], key
    assert args["experts_held"] == [0, 36] and args["head_dim"] == 128
    entry = next(c for c in cells[CELL].manifest["configs"]
                 if c["name"] == "granite-4.0-h-small-d10")
    assert entry["reduced"] == reduced
    assert set(reduced) <= set(config["reduced_why"])
    assert {"deployment", "departures", "assumed", "check"} <= set(config)
    assert config["builder"]["expect"]["layer_groups"] == {"full": 1,
                                                           "state": 9}
    assert config["builder"]["expect"]["prefix_cache"] is False


def test_the_traffic_is_short_chat_over_many_slots(cells):
    traffic = cells[CELL].traffic
    assert (traffic["loop"], traffic["clients"], traffic.get("prefix"),
            traffic["schedule_seed"], traffic["pool"]) == (
                "closed", 80, None, 36, 8192)
    sizes = set()
    for seed in (1, 2147483999):
        requests = loadgen.schedule(dict(traffic, pool=320), seed, 100352,
                                    0.0)
        own = np.asarray([len(r.prompt) for r in requests])
        out = np.asarray([r.max_new_tokens for r in requests[80:]])
        assert own.min() >= 64 and own.max() <= 2048
        assert out.min() >= 64 and out.max() <= 512
        sizes.add((int(own.sum()), int(out.sum())))
        assert 500 < own.mean() < 640 and 190 < out.mean() < 240
    assert len(sizes) == 1      # one realisation whatever the run's seed


def test_long_shared_is_a_cell_of_the_opt_configuration_as_data(cells):
    shared, decode = cells[SHARED], cells[DECODE]
    assert shared.config == decode.config
    assert {m["name"] for m in shared.end_to_end} == {
        m["name"] for m in decode.end_to_end}
    assert {m["name"] for m in shared.per_layer} == {
        m["name"] for m in decode.per_layer}
    traffic = shared.traffic
    assert (traffic["loop"], traffic["clients"], traffic["prefix"]) == (
        "closed", 24, {"count": 16, "tokens": 1536, "zipf_a": 1.0,
                       "block": 16})
    requests = loadgen.schedule(dict(traffic, pool=256), 7, 50272, 0.0)
    asked = [r.prefix_id for r in requests]
    own = [len(r.prompt) - 1536 for r in requests]
    assert set(asked) == set(range(16)) and min(own) >= 16 and max(own) <= 256
    assert asked.count(0) > 4 * asked.count(15)
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert longest <= 1824 < decode.config["builder"]["model_args"][
        "max_positions"]
    # the documents do not fit the pool: 24,576 tokens against 20,480
    engine = decode.config["builder"]["engine"]
    assert 16 * 1536 > engine["num_pages"] * engine["page_size"]


def test_the_runner_the_reference_and_the_flops_load(cells):
    cell = cells[CELL]
    runner = cell.module("runners", cell.config["runner"])
    assert callable(runner.run) and callable(runner.verdict)
    assert runner.check_lengths(cell.config["check"], cell.traffic) == [
        1300, 40, 700, 2000, 300]
    assert runner.CONTROLS == ("float8", "state_bf16")
    assert runner.state_error(np.ones((2, 3, 4)), np.ones((2, 3, 4))) == 0
    assert runner.state_error(np.ones((2, 3, 4)),
                              1.01 * np.ones((2, 3, 4))) == pytest.approx(
                                  0.01)
    assert callable(cell.module(
        "reference", cell.config["reference"]).next_token_logits)
    flops = cell.flops()
    # by hand: ONE attention layer, 4,096 B a token, 2 x 2 x 32 x 128
    # operations a pair; the window arguments count nothing
    ops, nbytes = flops.ragged_call(cell.config, 50000, 7, 50000, 7)
    assert nbytes == 50000 * 4096 and ops == 50000 * 16384
    ops, nbytes = flops.moe_call(cell.config, 3200, 36)
    assert nbytes == 36 * 3 * 4096 * 768 * 2
    assert ops == 3200 * 3 * 2 * 4096 * 768
    # a row's state and tail, read and written: 64 rows x 9 layers
    assert flops.ssm_update_bytes(cell.config, 576) == 576 * 2 * (
        4194304 + 50688)
    assert flops.ssm_scan_flops(cell.config, 1) == (
        2 * 2 * 8192 * 128 + 128 * 2 * (128 + 8192))
    # what the other expert cells' readers read of this configuration
    assert cell.config["builder"]["model_args"]["n_routed_experts"] == 36


UPDATE = ("%fusion.525 = (f32[65,128,64,128]{3,2,1,0:T(8,128)}, "
          "f32[65,128,64]{2,1,0:T(8,128)S(1)}) fusion(f32[65,128,64,128]"
          "{3,2,1,0:T(8,128)} %flat_11_.1, f32[65,128]{1,0:T(8,128)S(1)} "
          "%get-tuple-element.7376), kind=kLoop, calls=%fused_computation.896")
TAIL = ("%broadcast_select_fusion.3 = bf16[65,3,8448]{2,0,1} fusion("
        "bf16[65,3,8448]{2,0,1} %p), kind=kLoop, calls=%fused_computation.7")
IN_PROJ = ("%fusion.91 = f32[576,16768]{1,0:T(8,128)} fusion(bf16[576,4096]"
           "{1,0} %x, bf16[4096,16768]{1,0} %w), kind=kOutput, calls=%f.1")
LOOP = ("%while.395 = (s32[]{:T(128)}, f32[576,8192]{1,0:T(8,128)}, "
        "f32[65,128,64,128]{3,2,1,0:T(8,128)}, bf16[65,3,8448]{2,1,0}) "
        "while((s32[], f32[576,8192]) %tuple.3), condition=%c, body=%b")
BLOCKS = ("%while.12 = (s32[]{:T(128)}, f32[576,8192]{1,0}, f32[128,64,128]"
          "{2,1,0}) while((s32[]) %tuple.9), condition=%c2, body=%b2")
IN_LOOP = ("%fusion.7 = f32[128,256,256]{2,1,0} fusion(f32[256,128]{1,0} "
           "%cum), kind=kLoop, calls=%fused_computation.12")
SLOT_WRITE = ("%fusion.222 = f32[65,128,64,128]{3,2,1,0} fusion("
              "f32[65,128,64,128]{3,2,1,0} %gte, f32[1,128,64,128]{3,2,1,0} "
              "%s), kind=kLoop, calls=%fused_computation.93")
OTHER_LOOP = ("%while.2 = (s32[]{:T(128)}, f32[576,4096]{1,0}) while("
              "(s32[]) %t), condition=%c3, body=%b3")
GROUPED = ("%ragged-dot-none.3 = f32[5760,1536]{1,0} custom-call(s32[1]{0}"
           " %get-tuple-element.44, s32[37]{0} %get-tuple-element.45, "
           "bf16[576,8192]{1,0} %not_a_row_of_the_scan), "
           "custom_call_target=\"tpu_custom_call\"")
NORM = ("%fusion.5 = f32[576,4096]{1,0} fusion(f32[576,4096]{1,0} %p), "
        "kind=kLoop, calls=%fused_computation.2")


def test_the_rule_tells_the_state_space_operations_by_their_arrays(cells):
    from benchmarks.trace import state_ops

    found = state_ops.shapes(cells[CELL].config)
    assert found == ("[65,128,64,128]", "[65,3,8448]",
                     (",16768]", ",8448]", ",8192]"))
    assert state_ops.shapes(cells[DECODE].config) is None
    ms = 1_000_000
    events = [(UPDATE, 0, 2 * ms), (TAIL, 2 * ms, ms), (IN_PROJ, 3 * ms, ms),
              (LOOP, 4 * ms, 6 * ms), (BLOCKS, 4 * ms, 4 * ms),
              (IN_LOOP, 4 * ms, 3 * ms), (NORM, 7 * ms, ms),
              (SLOT_WRITE, 8 * ms, 2 * ms), (GROUPED, 10 * ms, ms),
              (OTHER_LOOP, 11 * ms, 2 * ms), (NORM, 11 * ms, 2 * ms),
              (NORM, 13 * ms, ms)]
    kinds = [kind for kind, _, _ in state_ops.classify(events, *found)]
    # the loops are no leaves; what they hold is the scan whatever it
    # names; a grouped product is nobody's whatever it names
    assert kinds == ["update", "update", "projections", "scan", "scan",
                     "scan", None, None, None]


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (name, start_ns,
                                                      duration_ns)
        self.stats = ()


@pytest.fixture
def profile(monkeypatch, tmp_path):
    """A profile of two steps: an update of 2 ms, a tail's fusion and a
    projection of 1 ms each, a norm, and in the second step a scan's
    loop of 4 ms; the window cuts the first update in half."""
    import jax

    from benchmarks.trace import reduce, state_ops

    ms = 1_000_000
    ops = [_Event(UPDATE, 0, 2 * ms), _Event(TAIL, 2 * ms, ms),
           _Event(IN_PROJ, 3 * ms, ms), _Event(NORM, 4 * ms, ms),
           _Event(UPDATE, 5 * ms, 2 * ms), _Event(TAIL, 7 * ms, ms),
           _Event(IN_PROJ, 8 * ms, ms), _Event(LOOP, 9 * ms, 4 * ms),
           _Event(IN_LOOP, 9 * ms, 3 * ms), _Event(SLOT_WRITE, 12 * ms, ms),
           _Event(GROUPED, 13 * ms, ms)]
    planes = [
        types.SimpleNamespace(name="/host:CPU", lines=[types.SimpleNamespace(
            name="python", events=[_Event(reduce.WINDOW_SPAN, ms, 13 * ms)])]),
        types.SimpleNamespace(name=reduce.DEVICE_PLANE + "0", lines=[
            types.SimpleNamespace(name=reduce.OPS_LINE, events=ops)])]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(
        lambda path: types.SimpleNamespace(planes=planes)))
    monkeypatch.setattr(reduce, "find_xplane", lambda trace_dir: str(tmp_path))
    state_ops._ops.cache_clear()
    yield
    state_ops._ops.cache_clear()


def _obs(cells, **more):
    return dict({"cell": cells[CELL], "config": cells[CELL].config,
                 "trace": {"busy_s": 0.013, "window_s": 0.013},
                 "peaks": {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
                 "result": {"window_s": 0.026, "counters": {
                     "generation.ssm_rows_updated": 2 * 576}}}, **more)


def test_the_time_share_reads_the_three_kinds_inside_the_window(cells,
                                                                profile):
    from benchmarks.trace import state_ops

    read = cells[CELL].module("layer_metrics", "ssm.time_share").read
    got = state_ops.seconds(_obs(cells))
    assert got == pytest.approx({"update": 0.005, "projections": 0.002,
                                 "scan": 0.004})
    assert read(_obs(cells)) == pytest.approx(100 * 0.011 / 0.013)
    assert read(_obs(cells, trace=None)) is None
    # a program without state-space layers (any other configuration)
    assert read(dict(_obs(cells), config=cells[DECODE].config)) is None


def test_the_update_s_roofline_is_the_counter_s_bytes_over_the_update_s_time(
        cells, profile):
    read = cells[CELL].module("layer_metrics",
                              "kernel.ssm_update_roofline").read
    # 1,152 (row, layer) pairs over a 26 ms window against 5 ms of
    # update in a 13 ms traced part
    least = 1152 * 2 * (4194304 + 50688) / 819e9
    assert read(_obs(cells)) == pytest.approx(
        100 * (least / 0.026) / (0.005 / 0.013))
    assert read(_obs(cells, peaks=None)) is None
    assert read(_obs(cells, result={"window_s": 0.026,
                                    "counters": {}})) is None


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1500)


def test_the_serving_cell_rehearses_on_the_cpu_and_is_correct():
    done = _run("benchmarks/run.py", "--workload", CELL, "--seed",
                "2147483936", "--seconds", "4", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20 and line["rehearsal"] is True
    metrics = line["metrics"]
    # counts only on the CPU: no time under a metric's name
    assert set(metrics) == {
        "engine.row_utilization", "step.compiles_in_window",
        "kernel.ragged.grid_utilization", "moe.load_max_over_mean"}
    assert metrics["step.compiles_in_window"]["value"] == 0
    assert 1.0 <= metrics["moe.load_max_over_mean"]["value"] <= 4.0
    checks = line["checks"]
    assert checks["agreeing_share"]["value"] == 1.0
    assert checks["slot_of_the_last_request"]["value"] == \
        checks["slot_of_the_last_request"]["limit"] == 0
    assert checks["states_started_from_zero"]["value"] == 5
    assert checks["state_relative_error"]["value"] <= 1e-5
    assert checks["pages_held_after_the_check"]["value"] == 0


def test_the_controls_in_a_lower_precision_are_not_correct():
    """The controls on the tiny preset: every matrix rounded to float8
    e4m3 fails the tokens' limits; the state kept in bfloat16 serves the
    float32 reference's tokens and fails the state's limit alone (its
    rounding walks over a head's memory: 0.3 % here, against the
    preset's 1e-5 and a float32 path's 3e-7)."""
    done = _run("tools/precision_control.py", "--workload", CELL, "--seed",
                "2147483951", "--rehearse")
    assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-2000:])
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["control"] == "float8_e4m3fn" and line["correct"] is False
    assert len(line["requests"]) == 5
    state = line["state_bf16"]
    assert state["correct"] is False
    assert 1e-4 < state["state_relative_error"] < 2e-2
    assert max(max(r["short"]) for r in state["requests"]) <= 1e-4
