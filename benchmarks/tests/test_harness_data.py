"""The harness is driven by data: a later PR adds a configuration, a
traffic mix, a per-layer metric and a cell as files and manifest entries,
and edits no file that is there."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

ROOT = manifest.ROOT


def _digest(root):
    out = {}
    for base, dirs, files in os.walk(os.path.join(root, "benchmarks")):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_manifest_obeys_the_contract():
    m = manifest.load(ROOT)           # validate() raises on a breach
    assert m["command"] == ["python3", "benchmarks/run.py"]
    assert m["paths"] == ["benchmarks"]
    assert 1 <= m["run_seconds"] <= 51
    cells = m["workloads"]
    assert [w["chips"] for w in cells].count(4) == 1
    assert 4 * [w["chips"] for w in cells].count(4) <= max(len(cells), 4)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert manifest.NAME.match(entry["name"]), entry["name"]
    for metric in m["end_to_end"]:
        assert metric["better"] in ("higher", "lower")
        assert 0.01 <= metric["bound"] <= 0.1
        assert manifest.metrics_for(m, "end_to_end", cells[0]["name"])
    for metric in m["per_layer"]:
        assert metric["better"] in ("higher", "lower")
        assert "bound" not in metric and metric["layer"] and metric["moves"]
        # its reader is a file of its own
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", metric["name"] + ".py"))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for w in cells:
        cell = manifest.Cell(m, w["name"], ROOT)
        names = {x["name"] for x in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert cell.config["source"].startswith("https://")


@pytest.mark.parametrize("breach", [
    lambda m: m["workloads"][0].update(name="bad name"),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
    lambda m: m["end_to_end"][0].update(bound=0.2),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["per_layer"][0].pop("workloads"),
    lambda m: m["per_layer"][0].update(layer="flash kernel"),
    lambda m: m["configs"][0]["reduced"].append("no_such_key"),
    lambda m: m.update(extra=1),
])
def test_a_breach_is_refused(breach):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    breach(m)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m, ROOT)


NEW_CONFIG = {
    "source": "https://example.org/toy-lm/config.json",
    "runner": "serve_engine",
    "reference": "causal_lm",
    "builder": {
        "model_args": {"vocab_size": 128, "num_layers": 1, "num_heads": 2,
                       "head_dim": 8, "mlp_ratio": 2, "max_positions": 128},
        "engine": {"num_pages": 64, "page_size": 4, "max_decode_slots": 2,
                   "kv_backend": "device", "step_mode": "ragged",
                   "use_kernel": True, "prefill_chunk_tokens": 8,
                   "prefix_cache": True},
        "expect": {"step_mode": "ragged", "kernel_path": "ragged:pallas",
                   "pools": "DeviceKVPool", "chunked": True,
                   "prefix_cache": True},
    },
    "check": {"prompt_tokens": [5], "new_tokens": 2, "timeout_s": 120,
              "logit_margin": 0.001},
    "rehearsal": {},
}
NEW_TRAFFIC = {
    "kind": "serve", "why": "a mix no file of the harness knows",
    "loop": "closed", "clients": 3, "pool": 20000,
    "prompt_tokens": {"dist": "const", "value": 6},
    "output_tokens": {"dist": "uniform", "lo": 2, "hi": 5},
    "ramp_s": 0.5, "timeout_ms": 60000, "trace_s": 1.0,
}
NEW_METRIC = '''
"""Layer: scheduler.  Engine steps counted inside the window."""


def read(obs):
    return float(obs["result"]["counters"]["generation.steps_total"])
'''


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = _digest(root)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "toy-lm.json"), "w") as f:
        json.dump(NEW_CONFIG, f)
    with open(os.path.join(bench, "traffic", "tiny-closed.json"), "w") as f:
        json.dump(NEW_TRAFFIC, f)
    with open(os.path.join(bench, "layer_metrics", "engine.steps.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = "toy-lm.tiny-closed"
    m["configs"].append({"name": "toy-lm", "source": NEW_CONFIG["source"],
                         "file": "benchmarks/configs/toy-lm.json",
                         "reduced": [], "why": "a test's"})
    m["workloads"].append({"name": cell, "config": "toy-lm",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "a test's"})
    for metric in m["end_to_end"]:
        if metric["name"] == "serve_gap_ms_p95":
            metric["workloads"].append(cell)
    m["per_layer"].append({
        "name": "engine.steps", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_gap_ms_p95", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, "cache"))
    lines = {}
    for trace in (0, 1):
        run = subprocess.run(
            [sys.executable, os.path.join(bench, "run.py"), "--workload",
             cell, "--seed", "3", "--seconds", "3", "--trace", str(trace),
             "--rehearse"],
            cwd=root, env=env, capture_output=True, text=True, timeout=600)
        assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
        lines[trace] = json.loads(run.stdout.strip().splitlines()[-1])
    for line in lines.values():
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 0 and line["rehearsal"] is True
        assert line["device"]["platform"] == "cpu"
        # a rehearsal prints no time under a metric's name
        assert "busy_s" not in line["device"] and "breakdown" not in line
    assert lines[0]["metrics"] == {}
    assert lines[1]["metrics"]["engine.steps"]["value"] > 0
    assert set(lines[1]["metrics"]) == {"engine.steps"}
    # nothing that was there was edited
    after = _digest(root)
    assert {k: after[k] for k in before} == before


def test_no_accelerator_is_a_failure_and_prints_no_result():
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "bert-base.pretrain-s128", "--seed", "1", "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert not run.stdout.strip().startswith("{")
    assert "no accelerator" in run.stderr
