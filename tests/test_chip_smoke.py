"""``chip_smoke.py`` rehearsed on the CPU at the smoke config.

On the CPU the script itself must refuse to run (it measures nothing off
the chip). Its serving phase and its four-chip sharded-evaluation phase
still run here through the same entry points at smoke size, with Pallas in
interpret mode, so a change that breaks the chip's smoke test shows up in
the CPU suite first.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


@pytest.fixture
def restore_compile_cache():
    """The entry points turn JAX's persistent compile cache on for the
    process; put the test process back as it was."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()


def test_refuses_without_a_tpu(capsys, restore_compile_cache):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_serve_phase_matches_dense_replay(restore_compile_cache):
    s = chip_smoke.serve_phase(full_config=False, requests=3, frames=2, slots=2)
    assert s["requests_done"] == 3 and s["frames_served"] == 6
    assert s["fused_layers"] == 27
    assert s["tpu_custom_calls"] == 0  # interpret mode: no Mosaic kernel
    assert s["finite"] and s["detections_served"] > 0
    assert s["max_head_diff"] == 0.0 and s["head_values_differing"] == 0
    assert s["detections_differing"] == 0


def test_sharded_phase_on_four_devices():
    """The ``--chips 4`` phase and its checks at the smoke config, on four
    simulated CPU devices in a child process (the device count is fixed
    when a process starts its backend)."""
    root = Path(__file__).resolve().parents[1]
    code = textwrap.dedent("""
        import jax
        import chip_smoke
        devices = jax.devices()
        assert len(devices) == 4, devices
        s = chip_smoke.sharded_phase(full_config=False)
        chip_smoke.check_sharded(s, devices)
        assert s["one_chip_forward_devices"] == [[0]] * 4, s
        assert s["four_shard_forward_devices"] == [[0], [1], [2], [3]], s
        assert s["n_images"] == chip_smoke.EVAL_IMAGES
        print("SHARDED_PHASE_OK")
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SHARDED_PHASE_OK" in r.stdout


_DEVICES = [type("Device", (), {"id": i})() for i in range(4)]
_GOOD_SHARDED = {"four_shards": {"gather": "mesh"},
                 "one_chip_forward_devices": [[0], [0]],
                 "four_shard_forward_devices": [[0], [1], [2], [3]],
                 "reports_identical": True}


def test_sharded_checks_pass_a_good_summary():
    chip_smoke.check_sharded(_GOOD_SHARDED, _DEVICES)


@pytest.mark.parametrize("key,bad", [
    ("four_shards", {"gather": "host"}),
    ("one_chip_forward_devices", [[0], [1]]),
    ("one_chip_forward_devices", []),
    ("four_shard_forward_devices", [[0], [0], [0], [0]]),
    ("reports_identical", False),
])
def test_sharded_checks_fail_a_wrong_summary(key, bad):
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_sharded({**_GOOD_SHARDED, key: bad}, _DEVICES)


def test_compile_cache_location(monkeypatch, tmp_path, restore_compile_cache):
    """$JAX_COMPILATION_CACHE_DIR wins; without it the cache is the
    checkout's one fixed directory."""
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    path = compile_cache.enable_compile_cache()
    assert path == str(Path(chip_smoke.__file__).resolve().parent / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
