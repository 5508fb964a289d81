"""The bring-up contract: no path that hides the device.

``chip_smoke.py`` refuses a non-TPU platform unless ``--dry-run`` pins the
CPU rehearsal; the compile cache is placed by one function the caller can
override from outside; the peaks table errors on an unknown TPU and gives
no peak off-TPU; ``bench.py`` never exits clean past a recorded error.
"""

import importlib.util
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
    child_env.pop("BENCH_PLATFORM", None)
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
    from gofr_tpu.tpu.flops import (
        device_peak_flops,
        device_peak_hbm_bw,
        mbu,
        mfu,
    )

    assert device_peak_flops("TPU v5 lite", "tpu") == 197e12
    assert device_peak_hbm_bw("TPU v5 lite", "tpu") == 819e9
    assert device_peak_flops("TPU v5 lite", "tpu", quant="w8a8") == 2 * 197e12
    for lookup in (device_peak_flops, device_peak_hbm_bw):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            lookup("TPU v9 hyper", "tpu")
        assert lookup("cpu", "cpu") == 0.0
    # no peak, no utilization: a CPU run cannot export an MFU or MBU
    assert mfu(10**9, 100, 1.0, device_peak_flops("cpu", "cpu")) == 0.0
    assert mbu(10**9, 1.0, device_peak_hbm_bw("cpu", "cpu")) == 0.0


def test_device_probe_fails_on_a_tpu_kind_without_a_peak(
    monkeypatch, cache_dir_setting
):
    """The unknown kind is an error AT THE PROBE, before any runner."""
    import gofr_tpu.tpu.device as device_mod
    from gofr_tpu.config import EnvConfig
    from gofr_tpu.logging import Level
    from gofr_tpu.metrics import Registry
    from gofr_tpu.testutil import MockLogger

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v9 hyper"

    monkeypatch.setenv("MODEL_NAME", "echo")
    monkeypatch.setenv("WATCHDOG_DISPATCH_TIMEOUT_S", "0")
    monkeypatch.delenv("TPU_BOOT", raising=False)
    monkeypatch.setattr(device_mod.jax, "devices", lambda *a, **k: [_Dev()])
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        device_mod.new_device(EnvConfig(), MockLogger(Level.INFO), Registry())


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_bench_never_exits_clean_past_a_recorded_error():
    bench = _load_bench()
    assert bench._exit_code({"value": 12.5}, []) == 0
    assert bench._exit_code({"value": 12.5}, ["decode phase: HTTP 500"]) == 1
    assert bench._exit_code({"value": None}, []) == 1


def test_bench_without_a_tpu_fails_unless_the_platform_is_pinned():
    proc = _run("bench.py", env={"BENCH_MODEL": "echo"}, timeout=60)
    assert proc.returncode != 0
    artifact = json.loads(proc.stdout.strip().splitlines()[-1])
    assert artifact["value"] is None
    assert artifact["device"]["platform"] == "cpu"
    assert any("no TPU attached" in e for e in artifact["errors"])
    assert "boot_stages" not in artifact  # failed before any model was built


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
