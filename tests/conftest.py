"""Test configuration: run on a virtual 8-device CPU mesh.

Multi-"chip" testing story per SURVEY.md §4: tests run on CPU with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so pipeline/mesh code is
exercised across 8 fake devices without TPU hardware. Must be set before the
first jax backend initialization, hence at conftest import time.

Lock-order witness (docs/STATIC_ANALYSIS.md): with PIPEEDGE_LOCKDEP=1 the
suite runs with `analysis/lockdep.py` tracking every `make_lock` site —
the tier-1 tests exercise the runtime's REAL lock interleavings, so a new
lock-order cycle or blocking-call-under-lock introduced by a PR is
witnessed here. Each witnessing process (this one and any spawned
runtime.py fleet rank, which inherits the env) appends a one-JSON-line
report to PIPEEDGE_LOCKDEP_OUT at exit; the CI gate asserts zero cycles.
"""
import os

# must precede the first pipeedge_tpu import: the witness activates when
# analysis/lockdep.py loads, and locks created before that are untracked
if os.getenv("PIPEEDGE_LOCKDEP") == "1" \
        and not os.getenv("PIPEEDGE_LOCKDEP_OUT"):
    os.environ["PIPEEDGE_LOCKDEP_OUT"] = os.path.abspath(
        "lockdep_report.json")

import jax  # noqa: E402

from pipeedge_tpu.utils import force_host_cpu_devices  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    """Print the lockdep verdict at the end of a witnessed run (the JSON
    line itself is appended by the module's atexit hook)."""
    from pipeedge_tpu.analysis import lockdep
    st = lockdep.state()
    if st is None:
        return
    rep = st.report()
    print(f"\nlockdep: {len(rep['locks'])} locks, {rep['edges']} order "
          f"edges, {rep['threads']} threads, "
          f"{len(rep['cycles'])} cycle(s), "
          f"{len(rep['blocking_violations'])} blocking-under-lock; "
          f"report -> {os.getenv('PIPEEDGE_LOCKDEP_OUT')}")
    if rep["cycles"]:
        print(f"lockdep CYCLES: {rep['cycles']}")

# Must run before the first backend initialization (the device count is
# parse-once).
force_host_cpu_devices(8)

# XLA CPU's default matmul precision is reduced (bf16-like passes); golden
# parity tests against torch float32 need full fp32 accumulation.
jax.config.update("jax_default_matmul_precision", "highest")
