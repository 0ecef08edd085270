"""The four readers of what PR 24's tracing added to the program (phase
spans of the ragged step, the request's admission stamp, the kernel
grid's denominator): values on a hand-made `obs`, None where the source
is missing (the parent program has none of them), the manifest's
entries, and the CPU rehearsal of both serving cells."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import manifest
from benchmarks.harness.loadgen import Tracked

ROOT = manifest.ROOT
SERVING = ["opt-6.7b-d8.chat-steady", "opt-6.7b-d8.decode-closed"]
NEW = {"step.host_ms_mean": ("step", "program_span"),
       "engine.queue_wait_ms_p50": ("scheduler", "program_span"),
       "engine.prefill_ms_p50": ("scheduler", "program_span"),
       "kernel.ragged.grid_utilization": ("paged_kernel",
                                          "program_counter")}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(ROOT), SERVING[0], ROOT)


def _read(cell, name, obs):
    return cell.module("layer_metrics", name).read(obs)


def _request(due, submitted, admitted, first, with_stamps=True):
    t = Tracked(None, due)
    t.token_s = [] if first is None else [first, first + 0.1]
    t.handle = types.SimpleNamespace(submitted_s=submitted,
                                     first_token_s=first)
    if with_stamps:
        t.handle.admitted_s = admitted
    return t


def _obs(tracked, ttft="all", trace=None, counters=None):
    result = {"tracked": tracked, "counters": counters or {}}
    if ttft == "all":
        ttft = [t.token_s[0] - t.due_abs for t in tracked if t.token_s]
    if ttft is not None:
        result["ttft_s"] = ttft
    return {"result": result, "trace": trace, "clock": {}}


# (due, submitted, admitted, first token), seconds
REQUESTS = [(10.0, 10.001, 10.2, 11.4), (11.0, 11.002, 11.1, 13.1),
            (12.0, 12.0, 12.9, 13.3), (13.0, 13.001, 13.4, None)]


def test_queue_wait_and_prefill_split_time_to_first_token(cell):
    obs = _obs([_request(*r) for r in REQUESTS])
    wait = _read(cell, "engine.queue_wait_ms_p50", obs)
    prefill = _read(cell, "engine.prefill_ms_p50", obs)
    # the request without a first token is left out of both
    assert wait == pytest.approx(199.0)        # of 199, 98, 900
    assert prefill == pytest.approx(1200.0)    # of 1200, 2000, 400
    for t in obs["result"]["tracked"][:3]:
        h = t.handle
        assert (h.admitted_s - h.submitted_s) + (
            h.first_token_s - h.admitted_s) == pytest.approx(
                h.first_token_s - h.submitted_s, abs=1e-12)


def test_request_readers_take_the_requests_ttft_takes(cell):
    tracked = [_request(*r) for r in REQUESTS]
    # the window saw the first tokens of the second and third only
    inside = [t.token_s[0] - t.due_abs for t in tracked[1:3]]
    obs = _obs(tracked, ttft=inside)
    assert _read(cell, "engine.queue_wait_ms_p50", obs) == pytest.approx(
        (98.0 + 900.0) / 2)
    assert _read(cell, "engine.prefill_ms_p50", obs) == pytest.approx(
        (2000.0 + 400.0) / 2)
    # a result that does not say: every request with a first token
    obs = _obs(tracked, ttft=None)
    assert _read(cell, "engine.queue_wait_ms_p50", obs) == pytest.approx(
        199.0)


@pytest.mark.parametrize("name", ["engine.queue_wait_ms_p50",
                                  "engine.prefill_ms_p50"])
def test_request_readers_read_nothing_from_the_parent_program(cell, name):
    bare = [_request(*r, with_stamps=False) for r in REQUESTS]
    assert _read(cell, name, _obs(bare)) is None
    assert _read(cell, name, _obs([])) is None
    assert _read(cell, name, {"result": {}, "trace": None}) is None


def _spans(**phases):
    return {"spans": {"generation::" + k: v for k, v in phases.items()}}


def test_host_ms_mean_is_the_serial_phases_over_the_steps(cell):
    trace = _spans(
        # the step in flight as the trace began left its emit and its
        # accounts and no ragged_step; the one in flight at its end its
        # schedule, pack and dispatch: two dispatches, two steps
        ragged_step=[0.110],
        schedule=[0.0010, 0.0012], pack=[0.0008, 0.0008],
        dispatch=[0.0004, 0.0006], emit=[0.0015, 0.0017],
        # twice a step: inside ragged_step and after it
        account=[0.0002, 0.0001, 0.0002, 0.0001],
        # hidden behind the device, and the wait for it: not the host's
        post_dispatch=[0.0007, 0.0007], fetch=[0.105, 0.107],
        sample=[0.0001, 0.0001])
    got = _read(cell, "step.host_ms_mean", _obs([], trace=trace))
    assert got == pytest.approx((2.2 + 1.6 + 1.0 + 3.2 + 0.6) / 2)


@pytest.mark.parametrize("trace", [
    None,
    {"spans": {}},
    _spans(ragged_step=[0.111, 0.112]),           # the parent program
    _spans(ragged_step=[0.111], schedule=[0.001], pack=[0.001],
           dispatch=[0.001], emit=[0.001]),       # a phase is missing
    _spans(schedule=[0.001, 0.001], account=[0.001, 0.001]),  # idle polls
])
def test_host_ms_mean_reads_nothing_without_its_spans(cell, trace):
    assert _read(cell, "step.host_ms_mean", _obs([], trace=trace)) is None


def test_grid_utilization_is_live_cells_over_grid_cells(cell):
    obs = _obs([], counters={"generation.step_score_blocks": 96 * 450,
                             "generation.step_grid_cells": 2720 * 450,
                             "generation.steps_total": 450})
    assert _read(cell, "kernel.ragged.grid_utilization", obs) == \
        pytest.approx(100.0 * 96 / 2720)


@pytest.mark.parametrize("counters", [
    {},
    {"generation.step_score_blocks": 5},          # the parent program
    {"generation.step_score_blocks": 0,
     "generation.step_grid_cells": 0},            # the jnp reference path
])
def test_grid_utilization_reads_nothing_without_a_grid(cell, counters):
    obs = _obs([], counters=counters)
    assert _read(cell, "kernel.ragged.grid_utilization", obs) is None
    assert _read(cell, "kernel.ragged.grid_utilization",
                 {"result": {}, "trace": None}) is None


def test_the_manifest_accepts_the_new_entries():
    m = manifest.load(ROOT)                 # validate() raises on a breach
    entries = {e["name"]: e for e in m["per_layer"]}
    # present with what they say, wherever later entries left them standing
    for name, (layer, source) in NEW.items():
        e = entries[name]
        assert (e["layer"], e["source"], e["moves"]) == (
            layer, source, "serve_gap_ms_p95")
        assert set(SERVING) <= set(e["workloads"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    for name in SERVING:
        cell = manifest.Cell(m, name, ROOT)
        assert set(NEW) <= {e["name"] for e in cell.per_layer}
    training = manifest.Cell(m, "bert-base.pretrain-s128", ROOT)
    assert not set(NEW) & {e["name"] for e in training.per_layer}


@pytest.mark.parametrize("name", SERVING)
def test_rehearsal_of_a_serving_cell_still_ends_in_its_line(name, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", name, "--seed", "2147483659", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    # a rehearsal prints counts and no time under any name: the three
    # new readers of spans and stamps are left out with the old ones
    assert "busy_s" not in line["device"] and "breakdown" not in line
    units = {m["name"]: (m["unit"], m["source"])
             for m in manifest.load(ROOT)["per_layer"]}
    for metric in line["metrics"]:
        assert units[metric] == (line["metrics"][metric]["unit"],
                                 "program_counter")
        assert units[metric][0] not in ("ms", "s")
    # the rehearsal preset runs the ragged kernel in the interpreter, so
    # the grid is counted; its share is a count over a count
    share = line["metrics"]["kernel.ragged.grid_utilization"]["value"]
    assert 0 < share <= 100
    assert "engine.row_utilization" in line["metrics"]
