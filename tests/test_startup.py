"""The start-up helpers every CLI calls (pipeedge_tpu/utils/__init__.py):
where the compile cache goes, and the device lines a parent reads."""
import json
import os
import subprocess
import sys

from pipeedge_tpu import utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SHOW_CACHE_DIR = (
    "from pipeedge_tpu.utils import enable_compile_cache\n"
    "enable_compile_cache()\n"
    "import jax\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_dir_of_a_fresh_process(env):
    proc = subprocess.run([sys.executable, "-c", _SHOW_CACHE_DIR],
                          capture_output=True, text=True, cwd=REPO,
                          env=dict(env, PYTHONPATH=REPO), timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_goes_to_the_fixed_directory_in_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    assert _cache_dir_of_a_fresh_process(env) == utils.COMPILE_CACHE_DIR
    assert utils.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_compile_cache_named_from_outside_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper sets nothing."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert _cache_dir_of_a_fresh_process(env) == str(tmp_path)


def test_conftest_sets_no_compile_cache_of_its_own():
    import jax
    assert jax.config.jax_compilation_cache_dir \
        == os.environ.get("JAX_COMPILATION_CACHE_DIR")


def test_device_lines_parse(capsys):
    stamp = utils.report_devices()
    rows = utils.report_device_memory()
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("devices: ")
    assert json.loads(out[0][len("devices: "):]) == stamp
    assert stamp["platform"] == "cpu" and stamp["count"] == len(rows)
    assert json.loads(out[1][len("device_memory: "):]) == rows
    assert set(rows[0]) == {"id", "bytes_in_use", "peak_bytes_in_use"}
