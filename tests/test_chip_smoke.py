"""``chip_smoke.py`` never runs off the TPU, and the compile cache lives
where the rule says (``launch.compile_cache``)."""
import json
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    """On the CPU the smoke exits non-zero before building any model and
    its last line says ``"ok": false``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False
    assert "depth cut" not in proc.stdout, "no model may be built"


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before, \
        "with the variable set, JAX reads it and the code sets nothing"


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.cache_dir() == want
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_four_chip_phase_on_cpu_devices():
    """The ``--chips 4`` phase at smoke widths on four virtual CPU
    devices: the target is created placed (a layer per device, embed on
    the first, head on the last), and the overlapped ring and the async
    actors agree with the flush executor on the first verify step."""
    code = ("import chip_smoke as cs; cs.phase_four_chips(['--mode', "
            "'pipedec-db', '--requests', '4', '--new-tokens', '6', "
            "'--target-layers', '4', '--draft-layers', '2'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    for name in ("overlapped", "async"):
        line = next(x for x in proc.stdout.splitlines()
                    if x.startswith(f"{name} vs flush"))
        assert "3 slot(s) with the same root token" in line, line
    assert "chip 3 (stage 3): target layer 3, final_norm, lm_head" \
        in proc.stdout
