"""chip_smoke.py's runner, and the helpers the chip programs share: where
the compile cache goes and which device a Place names.  The phases
themselves run at toy widths in the slow lane; on the chip they are the
driver's gate."""
import os
import subprocess
import sys
import warnings

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402
from paddle_tpu.core import device  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    before = {name: getattr(jax.config, name) for name in CACHE_OPTIONS}
    yield before
    for name, value in before.items():
        jax.config.update(name, value)


def test_cache_dir_from_the_environment_is_left_to_jax(
        monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    # no path is set in code; the storage threshold is not a path
    assert (jax.config.jax_compilation_cache_dir
            == restore_cache_config["jax_compilation_cache_dir"])
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_cache_threshold_from_the_environment_is_left_to_jax(
        monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")
    compile_cache.enable_compile_cache()
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == restore_cache_config[
                "jax_persistent_cache_min_compile_time_secs"])


def test_flops_fall_back_to_the_hand_model_without_a_cost_analysis():
    # Lowered.cost_analysis() is None for a TPU lowering
    # (tests/test_chip_compile.py pins that on the installed JAX)
    assert bench._measured_flops(None, 7) == (7.0, "analytic")
    assert bench._measured_flops({"bytes accessed": 1.0}, 7) == (
        7.0, "analytic")
    assert bench._measured_flops({"flops": 3.0}, 7) == (
        3.0, "xla_cost_analysis")


def test_place_refuses_a_device_id_out_of_range():
    n = len(jax.devices("cpu"))
    assert device.Place("cpu", n - 1).jax_device() == jax.devices("cpu")[-1]
    with pytest.raises(RuntimeError, match="out of range"):
        device.Place("cpu", n).jax_device()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError, match="out of range"):
            device.TPUPlace(n).jax_device()


def test_accelerator_place_on_a_cpu_host_says_so_once():
    device._warn_accelerator_place_on_cpu.cache_clear()
    with pytest.warns(UserWarning, match="no accelerator"):
        assert device.TPUPlace(0).jax_device().platform == "cpu"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        device.TPUPlace(1).jax_device()


@pytest.fixture
def run_phases(monkeypatch):
    """Runs main(--rehearse) over the given phases in place of the real
    ones.  main sets the platform in os.environ and turns the compile
    cache on; neither may outlive the test."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "(off under test)")
    monkeypatch.setattr(chip_smoke, "FOUR_CHIP_PHASES", {})

    def run(phases):
        monkeypatch.setattr(chip_smoke, "PHASES", phases)
        chip_smoke.main(["--rehearse"])
    return run


def quick(jax_mod, size):
    """A phase that compiles one program."""
    assert size is chip_smoke.TOY
    jax_mod.jit(lambda x: x * 2)(3.0).block_until_ready()


def test_runner_reports_a_passing_phase_and_prints_no_result(
        run_phases, capsys):
    run_phases({"dygraph": quick})
    out = capsys.readouterr().out
    assert "--- dygraph passed" in out and "REHEARSAL" in out
    assert "PASS\n" not in out and '"ok"' not in out


def test_a_failing_phase_fails_the_run(run_phases, capsys):
    def broken(jax_mod, size):
        """A phase whose loss is not finite."""
        raise FloatingPointError("loss is nan")

    with pytest.raises(FloatingPointError):
        run_phases({"static": broken, "kernels": quick})
    out = capsys.readouterr().out
    assert "passed" not in out and "REHEARSAL" not in out
    assert '"ok"' not in out


@pytest.mark.slow   # every real phase at toy widths: ~90 s of CPU
def test_full_rehearsal_runs_every_phase():
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    for name in list(chip_smoke.PHASES) + list(chip_smoke.FOUR_CHIP_PHASES):
        assert f"--- {name} passed" in proc.stdout, proc.stdout[-3000:]
    assert proc.stdout.rstrip().endswith(
        "REHEARSAL: toy widths on the CPU; says nothing of the chip")
