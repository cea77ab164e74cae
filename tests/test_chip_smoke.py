"""chip_smoke.py's CPU rehearsal, so the chip script cannot rot between runs.

The one-chip service path runs in this process at tiny sizes; the
four-device phase runs in a child on four virtual CPU devices.  Also pins
the script's refusal to run without a TPU, its numpy reference checks,
and where the persistent compilation cache goes.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def _phases(stdout: str) -> tuple[dict, dict]:
    lines = [json.loads(l) for l in stdout.strip().splitlines()]
    return {p["phase"]: p for p in lines[:-1]}, lines[-1]


def test_rehearsal_service_path(smoke, tmp_path, capsys):
    assert smoke.main(["--rehearsal", "--out", str(tmp_path)]) == 0
    phases, last = _phases(capsys.readouterr().out)
    # the count is whatever this process's CPU backend was started with
    # (another test module in the same worker may have forced more)
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())},
    }
    assert phases["cold_batch"]["merge_dispatches"] == 1
    assert phases["warm_batch"]["programs_lowered"] == 0
    bits = phases["reopen_bit_identical"]
    assert bits["identical"] == bits["of"] == smoke.TINY.panels
    assert phases["reference"]["answers_checked"] == smoke.TINY.panels
    assert phases["after_reopen_health"]["degraded_served"] == 0
    assert os.listdir(tmp_path) == []  # the service's data directory is gone


def test_rehearsal_four_device_phase(tmp_path):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
    )
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearsal", "--four-chip"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    phases, last = _phases(out.stdout)
    assert last["ok"] is True and last["device"]["count"] == 4
    for name in ("distributed_histogram", "distributed_histogram_hierarchical"):
        assert phases[name]["failures"] == 0
        assert phases[name]["all_gather_groups"]
        assert all(sorted(g) == [0, 1, 2, 3] for g in phases[name]["all_gather_groups"])


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 2
    assert capsys.readouterr().out == ""  # no result line


def test_reference_checks_reject_wrong_answers(smoke):
    rng = np.random.default_rng(0)
    pooled = np.sort(rng.gumbel(size=4096).astype(np.float32))
    beta = 16
    cuts = np.arange(beta + 1) * pooled.size // beta
    bounds = pooled[np.minimum(cuts, pooled.size - 1)]
    sizes = np.diff(cuts).astype(np.float32)
    assert smoke.check_answer(bounds, sizes, 2.0, pooled, beta) == []

    moved = bounds.copy()
    moved[5] = pooled[cuts[5] + 40]  # a boundary 40 ranks off
    assert smoke.check_answer(moved, sizes, 2.0, pooled, beta)
    absent = bounds.copy()
    absent[3] = np.nextafter(absent[3], np.float32(np.inf))
    assert any("not in the data" in f
               for f in smoke.check_answer(absent, sizes, 2.0, pooled, beta))
    lost = sizes.copy()
    lost[0] -= 1.0
    assert any("sum" in f
               for f in smoke.check_answer(bounds, lost, 2.0, pooled, beta))


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
