"""The step's device time by part (`benchmarks/trace/scopes.py`): the
join of a profile's leaves with the program's map on a hand-made trace
(module containment, self time, unnamed leaves, the step programs'
durations), the seven readers' None where nothing can be read, and their
manifest entries."""
import types

import pytest

from benchmarks.harness import manifest
from benchmarks.trace import scopes

ROOT = manifest.ROOT
PARTS = ("hand_over", "embed", "attention", "mlp", "experts",
         "state_space", "head")
READERS = ["step.attention.time_share", "step.mlp.time_share",
           "step.experts.time_share", "step.state_space.time_share",
           "step.head.time_share", "step.unnamed_share",
           "step.device_ms_p50"]

# two steps of the 128-page program, a small program of another name
# between them; times in ns.  The window [100, 1900) cuts the first
# step's first operation and leaves the second step out of `step_ms`.
MODULES = [("jit_ragged_step_p128(7)", 0, 900),
           ("jit_forget(9)", 950, 40),
           ("jit_ragged_step_p128(7)", 1000, 1000)]
OPS = [
    ("%fusion.1 = f32[80,4096] fusion(...), kind=kLoop", 0, 300),
    ("%ragged_paged_attention.2 = f32[32,80,128] custom-call(...)",
     300, 200),
    ("%while.3 = (s32[], f32[8]) while(...)", 500, 300),
    ("%convolution.4 = f32[8] convolution(...)", 550, 100),
    ("%copy.5 = f32[8] copy(...)", 700, 50),          # no map entry
    ("%fusion.6 = f32[16] fusion(...)", 820, 40),      # outside parts
    ("%fusion.1 = f32[1] fusion(...)", 960, 20),       # another program
    ("%fusion.1 = f32[80,4096] fusion(...), kind=kLoop", 1000, 300),
    ("%ragged_paged_attention.2 = f32[32,80,128] custom-call(...)",
     1300, 200),
]
SCOPES = {"jit_ragged_step_p128": {
    "fusion.1": "mlp", "ragged_paged_attention.2": "attention",
    "while.3": "state_space/scan/while", "convolution.4":
    "state_space/scan/while/body", "fusion.6": ""}}


def test_leaves_are_joined_by_module_and_instruction():
    found = scopes.split((100, 1900), MODULES, OPS, SCOPES, PARTS)
    s = found["parts"]
    # the first fusion clipped to the window, twice over two steps
    assert s["mlp"] == pytest.approx((200 + 300) / 1e9)
    assert s["attention"] == pytest.approx((200 + 200) / 1e9)
    # the loop's own time is what its body leaves: 300 - 100 - 50
    assert s["state_space"] == pytest.approx((150 + 100) / 1e9)
    assert s["head"] == s["embed"] == 0.0
    # the copy (no entry), the fusion outside every part and the other
    # program's fusion of the same instruction name
    assert found["unnamed_s"] == pytest.approx((50 + 40 + 20) / 1e9)
    busy = 200 + 200 + 300 + 40 + 20 + 300 + 200
    assert (sum(s.values()) + found["unnamed_s"]) * 1e9 == \
        pytest.approx(busy)
    # only the step program's event wholly inside the window
    assert found["step_ms"] == []
    assert scopes.split((0, 2000), MODULES, OPS, SCOPES,
                        PARTS)["step_ms"] == [900 / 1e6, 1000 / 1e6]


def test_a_leaf_outside_every_module_is_unnamed():
    found = scopes.split((0, 2000), [], OPS[:2], SCOPES, PARTS)
    assert sum(found["parts"].values()) == 0.0
    assert found["unnamed_s"] == pytest.approx(500 / 1e9)
    assert scopes.module_name("jit_ragged_step_p16(3)") == \
        "jit_ragged_step_p16"


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(ROOT), "opt-6.7b-d8.decode-closed",
                         ROOT)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_is_none_without_a_trace(cell, name):
    obs = {"cell": cell, "trace": None, "result": {}, "clock": {}}
    assert cell.module("layer_metrics", name).read(obs) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_is_none_without_a_profile(cell, name, tmp_path):
    """A reduced trace but no profile on disk (the cell's trace
    directory under a checkout that holds none)."""
    obs = {"cell": types.SimpleNamespace(root=str(tmp_path),
                                         name=cell.name),
           "trace": {"busy_s": 1.0}, "result": {}, "clock": {}}
    assert cell.module("layer_metrics", name).read(obs) is None


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_each_reader(name):
    entry = next(m for m in manifest.load(ROOT)["per_layer"]
                 if m["name"] == name)
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        "step", "device_trace", "serve_gap_ms_p95")
    assert "opt-6.7b-d8.decode-closed" in entry["workloads"] or \
        name in ("step.experts.time_share", "step.state_space.time_share")
