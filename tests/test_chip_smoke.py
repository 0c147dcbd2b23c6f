"""``chip_smoke.py`` off the chip: it refuses the CPU, its phases agree
at a tiny size, and the compile cache lands where it must."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from benchmarks.common import num_buckets_for, use_compile_cache  # noqa: E402


@pytest.mark.parametrize("keys,buckets", [
    (4096, 1 << 17), (100_000, 1 << 17),    # every measured row: 2^17
    (1 << 22, 1 << 22), ((1 << 22) + 1, 1 << 23)])
def test_index_scales_with_keys(keys, buckets):
    assert num_buckets_for(keys) == buckets


def test_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py", "--keys", "4096"],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_phases_agree_at_tiny_size(capsys):
    """Every phase after the device check, on this CPU: the jit and
    host twins agree and the compiled executor ran ops."""
    chip = chip_smoke.run(keys=4096, batches=1, seed=1)
    assert chip.telemetry.counts["device_ops"] > 0
    out = capsys.readouterr().out
    assert "reduced: keys 2^25 -> 2^12" in out
    for mix in chip_smoke.SMOKE_MIXES:
        assert f"agree: {mix} stats=" in out
    line = [ln for ln in out.splitlines() if ln.startswith("host:")][0]
    json.loads(line.split("reason ", 1)[1])


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """A set JAX_COMPILATION_CACHE_DIR wins and nothing else is set;
    otherwise the cache sits at one fixed, git-ignored checkout path."""
    was = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(REPO, ".jax_cache")
            assert use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            entry = os.path.join(want, "entry")
            ignored = subprocess.run(["git", "check-ignore", "-q", entry],
                                     cwd=REPO).returncode == 0
            assert ignored or not os.path.isdir(os.path.join(REPO,
                                                             ".git"))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
