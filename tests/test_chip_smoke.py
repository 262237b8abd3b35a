"""The chip bring-up surface, checked on the CPU: ``chip_smoke.py
--rehearse`` end to end, its refusal to run outside a checkout, the
server's ``device`` stats block, and where ``_xla_env`` puts JAX's
persistent compilation cache."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.core import DecodeConfig, VideoStore

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _run_smoke(script, *args, timeout=600):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)   # the script finds its own sources
    return subprocess.run([sys.executable, script, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearse_passes_and_ends_with_device_line():
    out = _run_smoke(os.path.join(ROOT, "chip_smoke.py"), "--rehearse")
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    facts = "\n".join(lines[:-1])
    for fact in ("ingest_s", "first_scan_s", "(compile included)",
                 "decoded 0 tiles", "max abs error vs numpy oracle",
                 "SIGTERM: server exited 0"):
        assert fact in facts


def test_outside_a_checkout_fails_without_result(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), lone)
    out = _run_smoke(str(lone), timeout=60)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("backend", ["numpy", "batched"])
def test_stats_device_block(backend):
    with VideoStore(decode=DecodeConfig(backend=backend)) as store:
        dev = store.stats()["device"]
    if backend == "numpy":
        assert dev is None   # never starts a JAX backend
        return
    import jax
    assert dev["platform"] == jax.devices()[0].platform
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(dev["ids"]) == jax.device_count()
    assert dev["visible_chips"] == os.environ.get("TPU_VISIBLE_CHIPS")


@pytest.mark.parametrize("preset", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, preset):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import _xla_env
    finally:
        sys.path.pop(0)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    if preset:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / preset))
    _xla_env.apply(argparse.Namespace(env=[], xla_flags=None))
    want = (str(tmp_path / preset) if preset else
            os.path.join(os.path.abspath(ROOT), ".jax_cache"))
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
