"""Subprocess wrapper for the native `sched-pipeline` scheduler binary.

Parity with /root/reference/src/pipeedge/sched/scheduler.py:24-73: builds the
CLI arguments, searches `app_paths` then the in-repo build dir then PATH, and
parses the YAML schedule from stdout into [{host: [layer_l, layer_r]}, ...].
"""
import hashlib
import logging
import os
import subprocess
from typing import Dict, List, Optional

import yaml

logger = logging.getLogger(__name__)

# in-repo build location (native/CMakeLists.txt)
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), 'native')
_REPO_BUILD_PATHS = [
    os.path.join(_NATIVE_DIR, 'build', 'sched-pipeline'),
]


_BUILD_FAILED = False

# digest of the sources the build tree was last built from: a build tree
# is git-ignored and outlives checkouts and copies, so its artifacts are
# trusted only while this stamp matches the sources beside it
_SOURCES_STAMP = os.path.join(_NATIVE_DIR, 'build', '.sources.sha256')


def _sources_digest() -> str:
    digest = hashlib.sha256()
    for name in sorted(os.listdir(_NATIVE_DIR)):
        path = os.path.join(_NATIVE_DIR, name)
        if os.path.isfile(path):
            digest.update(name.encode())
            with open(path, 'rb') as src:
                digest.update(src.read())
    return digest.hexdigest()


def _is_fresh(artifact: str) -> bool:
    """Whether `artifact` exists and was built from the current sources."""
    try:
        with open(_SOURCES_STAMP, encoding='utf8') as stamp:
            built_from = stamp.read().strip()
    except FileNotFoundError:
        return False
    return os.path.exists(artifact) and built_from == _sources_digest()


def build_native(force: bool = False,
                 artifact: Optional[str] = None) -> Optional[str]:
    """Build the in-repo native tree unless `artifact` is there and fresh;
    returns its path. `artifact` defaults to the `sched-pipeline` binary;
    other targets (e.g. libquantpack.so) pass their own path so a build
    tree that predates them still gets rebuilt.

    The reference ships its binary inside the wheel via py-build-cmake
    (pyproject.toml:36-52); for a source checkout we compile on first use so
    the build tree never needs to be committed. Returns None if no native
    toolchain is available; a failed build is cached so repeated calls don't
    re-run cmake.
    """
    global _BUILD_FAILED
    binary = artifact or _REPO_BUILD_PATHS[0]
    if _is_fresh(binary) and not force:
        return binary
    if _BUILD_FAILED and not force:
        return None
    build_dir = os.path.join(_NATIVE_DIR, 'build')
    os.makedirs(build_dir, exist_ok=True)
    try:
        # serialize concurrent builders (e.g. parallel test workers) on an
        # advisory file lock; the loser re-checks for the winner's binary
        import fcntl
        lock_f = open(os.path.join(build_dir, '.build-lock'), 'w')
    except (OSError, ImportError):
        lock_f = None
    try:
        if lock_f is not None:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            if _is_fresh(binary) and not force:
                return binary
        digest = _sources_digest()
        subprocess.run(['cmake', '-B', build_dir, '-G', 'Ninja', _NATIVE_DIR],
                       capture_output=True, check=True)
        subprocess.run(['ninja', '-C', build_dir], capture_output=True,
                       check=True)
        with open(_SOURCES_STAMP, 'w', encoding='utf8') as stamp:
            stamp.write(digest)
    except FileNotFoundError as exc:
        logger.warning("native toolchain unavailable (%s); cannot build "
                       "sched-pipeline", exc)
        _BUILD_FAILED = True
        return None
    except subprocess.CalledProcessError as exc:
        _log_cpe(exc)
        _BUILD_FAILED = True
        return None
    finally:
        if lock_f is not None:
            lock_f.close()
    if os.path.exists(binary):
        _BUILD_FAILED = False
        return binary
    _BUILD_FAILED = True
    return None


def _log_cpe(exc: subprocess.CalledProcessError) -> None:
    logger.error("Scheduler subprocess failed, return code: %d", exc.returncode)
    stdout = exc.stdout.decode().strip()
    if stdout:
        logger.info("stdout:\n%s", stdout)
    stderr = exc.stderr.decode().strip()
    if stderr:
        logger.error("stderr:\n%s", stderr)


def sched_pipeline(model_name: str, buffers_in: int, buffers_out: int,
                   batch_size: int, dtype: str = 'torch.float32',
                   models_file: Optional[str] = None,
                   dev_types_file: Optional[str] = None,
                   dev_file: Optional[str] = None,
                   app_paths: Optional[List[str]] = None) \
        -> List[Dict[str, List[int]]]:
    """Run the native scheduler; returns the stage list in layer order."""
    if app_paths is None:
        app_paths = []
    args = ['-i', str(buffers_in), '-o', str(buffers_out),
            '-b', str(batch_size), '-d', dtype, '-m', model_name]
    if models_file:
        args += ['-M', models_file]
    if dev_types_file:
        args += ['-T', dev_types_file]
    if dev_file:
        args += ['-D', dev_file]

    candidates = (list(app_paths)
                  + [p for p in _REPO_BUILD_PATHS if _is_fresh(p)]
                  + ['sched-pipeline'])
    proc = None
    last_missing = None

    def _try(app_path):
        nonlocal proc, last_missing
        try:
            proc = subprocess.run([app_path] + args, capture_output=True,
                                  check=True)
            return True
        except FileNotFoundError:
            last_missing = app_path
            return False
        except subprocess.CalledProcessError as exc:
            _log_cpe(exc)
            raise

    for app_path in candidates:
        if _try(app_path):
            break
    else:
        # every candidate missing: compile the in-repo binary on demand
        # (only now, so explicit app_paths / PATH installs take precedence
        # and we never run cmake when a binary already exists)
        built = build_native()
        if built is None or not _try(built):
            if _BUILD_FAILED:
                logger.error("Could not locate sched-pipeline and the "
                             "auto-build failed (see log above) - fix the "
                             "native toolchain or install a prebuilt "
                             "sched-pipeline on PATH")
            else:
                logger.error("Could not locate sched-pipeline (last tried "
                             "%r) - build it with: cmake -B native/build "
                             "native && ninja -C native/build", last_missing)
            raise FileNotFoundError('sched-pipeline')

    stderr = proc.stderr.decode().strip()
    if stderr:
        logger.warning(stderr)
    sched = yaml.safe_load(proc.stdout.decode())
    if sched is None:
        sched = []
    assert isinstance(sched, list)
    return sched
