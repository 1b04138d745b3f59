"""CPU rehearsal of ``chip_smoke.py``: every phase runs on a tiny city
through the real entry points, every answer matches the CPU oracle, and
the script then refuses the platform (``"ok": false``, nonzero exit)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("chips", (1, 4))
def test_smoke_rehearsal_matches_then_refuses_cpu(tmp_path, chips):
    cache = str(tmp_path / "jax-cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--chips", str(chips), "--rehearse", "--width", "24",
         "--height", "18",
         "--workdir", str(tmp_path / "work")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    lines = [json.loads(line) for line in res.stdout.splitlines()]
    phases = {d.get("phase"): d for d in lines}
    assert phases["setup"]["compile_cache"] == cache
    assert phases["check"]["matched"] == phases["check"]["sent"] > 0, (
        res.stdout, res.stderr)
    assert phases["check"]["failed"] == 0
    assert "not a TPU" in phases["failed"]["error"]
    # conftest's 8 virtual CPU devices reach the children
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}
    assert res.returncode != 0
    if chips == 1:
        serve = phases["serve"]
        assert serve["walk_kernel"] == "xla"
        assert serve["gateway_rc"] == 0
        # the gateway compiled every batch size before it listened:
        # the default max_batch of 64 is 7 powers of two, and the
        # client's frames compiled nothing more
        assert serve["compiles"] == 7
    else:
        assert len(set(phases["build"]["shard_devices"].values())) == 4


def test_refused_off_chip_before_any_phase(tmp_path):
    """Without a chip and without --rehearse the run stops after the
    platform probe: no data, no build, no server."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--workdir", str(tmp_path / "work")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    lines = [json.loads(line) for line in res.stdout.splitlines()]
    assert [d.get("phase") for d in lines[:-1]] == [
        "setup", "platform", "failed"]
    assert "not a TPU" in lines[-2]["error"]
    assert lines[-1]["ok"] is False and res.returncode != 0
