"""The bring-up contract: no path that hides the device.

``chip_smoke.py`` refuses a non-TPU platform unless ``--dry-run`` pins the
CPU rehearsal; the compile cache is placed by one function the caller can
override from outside; the peaks table errors on an unknown TPU and gives
no peak off-TPU.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(script: str, *args: str, env: dict | None = None, timeout: float = 120):
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, str(REPO / script), *args], cwd=REPO, env=child_env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_smoke_refuses_the_cpu_before_building_a_model():
    start = time.monotonic()
    proc = _run("chip_smoke.py", timeout=60)
    assert proc.returncode != 0
    assert time.monotonic() - start < 60
    assert "platform='cpu'" in proc.stderr  # names what it found
    assert "boot:" not in proc.stderr  # no server, no model
    assert proc.stdout.strip() == ""  # neither report nor verdict


@pytest.fixture
def cache_dir_setting():
    """The process-wide jax cache-dir setting, restored after the test."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_a_callers_directory_untouched(
    monkeypatch, tmp_path, cache_dir_setting
):
    import jax

    from gofr_tpu.tpu.device import configure_compile_cache

    placed = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert configure_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == cache_dir_setting  # sets nothing


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, cache_dir_setting
):
    import jax

    from gofr_tpu.tpu.device import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = configure_compile_cache(), configure_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_peaks_table_errors_on_an_unknown_tpu_and_has_no_cpu_peak():
    from gofr_tpu.tpu.flops import device_peak_flops, mfu

    assert device_peak_flops("TPU v5 lite", "tpu") == 197e12
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        device_peak_flops("TPU v9 hyper", "tpu")
    assert device_peak_flops("cpu", "cpu") == 0.0
    # no peak, no utilization: a CPU run cannot report an MFU
    assert mfu(10**9, 100, 1.0, device_peak_flops("cpu", "cpu")) == 0.0


def test_chaos_subprocess_replicas_stay_off_the_chip():
    from gofr_tpu.devtools.chaos import SubprocessReplica

    assert SubprocessReplica("r0")._env["JAX_PLATFORMS"] == "cpu"


@pytest.mark.slow
def test_smoke_dry_run_end_to_end(tmp_path):
    cache = str(tmp_path / "cache")
    proc = _run("chip_smoke.py", "--dry-run", timeout=600,
                env={"JAX_COMPILATION_CACHE_DIR": cache})
    assert proc.returncode == 0, proc.stderr[-2000:]
    report, verdict = map(json.loads, proc.stdout.splitlines())  # two lines
    # the last line is the driver's contract: exactly these keys
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": report["device_kind"],
        "count": report["device_count"],
    }}
    assert report["dry_run"] is True
    assert report["kernels"]["interpret"] is True
    assert report["requests"] == {"sent": 7, "ok": 7, "failed": 0}
    assert report["compiles_in_window"]["xla"] == 0
    assert report["compile_cache"] == {"dir": cache, "entries_at_start": 0}
    assert report["prefix"]["hits"] >= 1
    assert all(report["dispatches"][k] > 0
               for k in ("prefill", "prefill_chunk", "decode_chunk"))
