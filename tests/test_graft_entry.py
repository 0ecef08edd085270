"""Driver-contract tests: import __graft_entry__ and call it the way the
driver does, and run the two chip programs (bench.py, chip_smoke.py) where
there is no chip."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_entry_compiles_and_runs():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    arr = np.asarray(out)
    assert arr.ndim == 3 and np.isfinite(arr).all()


def test_dryrun_multichip_direct_call():
    """The driver imports and calls with jax possibly already initialized —
    under pytest the CPU backend is live with 8 virtual devices, so this
    exercises the in-process path."""
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_dryrun_multichip_subprocess_from_clean_env():
    """Simulate the driver's import-and-call from a process that has NOT
    configured jax at all (the round-1 rc=124 scenario)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    code = ("import __graft_entry__ as ge; ge.dryrun_multichip(4)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # per-leg machine-checkable status lines (VERDICT r2 #7)
    legs = {}
    for ln in proc.stdout.splitlines():
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict) and "leg" in rec:
            legs[rec["leg"]] = rec["ok"]
    assert legs.get("zero3_dp_tp_sp") is True, proc.stdout
    for leg, ok in legs.items():
        assert ok, f"leg {leg} failed: {proc.stdout}"


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_chip_programs_refuse_the_cpu(script):
    """bench.py and chip_smoke.py measure and prove the chip: with no
    accelerator they exit non-zero and print no result, whatever the
    backend could have computed."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert "no accelerator" in proc.stderr, proc.stderr[-2000:]
    for ln in proc.stdout.splitlines():
        assert not ln.lstrip().startswith("{"), proc.stdout
