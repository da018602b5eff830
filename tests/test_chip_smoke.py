"""``chip_smoke.py`` at tiny sizes on the CPU.

The script itself refuses anything but a TPU and keeps its full-size
defaults; these tests call its phase functions with small shapes so that
a change which breaks the chip check shows here first.
"""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core import nn2sql  # noqa: E402


def _run(code: str, **env):
    full_env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
                "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, env=full_env,
                          timeout=600)


def test_phases_pass_at_tiny_size(capsys):
    clock = cs.CompileClock()
    try:
        cs.phase_a(clock, spec=nn2sql.MLPSpec(64, 784, 16, 10))
        tiny = get_config("granite_3_8b", reduced=True)
        lm, params = cs.phase_b(clock, tiny, 40, batch=2, seq=64)
        cs.phase_c(clock, lm, params, max_len=64)
    finally:
        jax.monitoring.unregister_event_time_span_listener(clock._on)
    out = capsys.readouterr().out
    for name in ("A.dense.highest", "A.dense.default", "A.relational.highest",
                 "A.relational.default", "A.engines", "B.train", "C.serve"):
        assert f"{name}: " in out


def test_sharded_phase_on_four_virtual_devices():
    r = _run("import chip_smoke as cs\n"
             "from repro.configs.base import get_config\n"
             "cs.phase_sharded(cs.CompileClock(),"
             " get_config('granite_3_8b', reduced=True), batch=4, seq=64)\n",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "S.train_2x2:" in r.stdout


def test_refuses_a_cpu_and_prints_no_result():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_goes_to_one_fixed_place(env_dir, tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    r = _run("import jax\n"
             "from repro.launch.compile_cache import enable_compile_cache\n"
             "d = enable_compile_cache()\n"
             "print(d == jax.config.jax_compilation_cache_dir, d)\n", **env)
    assert r.returncode == 0, r.stderr[-3000:]
    same, path = r.stdout.split()
    assert same == "True"
    assert path == (str(tmp_path) if env_dir else str(ROOT / ".jax_cache"))
