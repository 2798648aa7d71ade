"""Runtime tracing: JAX profiler (XPlane/TensorBoard/Perfetto) integration.

The reference has NO trace-viewer integration anywhere (SURVEY.md §5.1 —
only wall-clock offline profiling and heartbeat CSVs). On TPU the profiler
is how you actually see MXU utilization, HBM traffic, and collective overlap,
so the runtime exposes it first-class:

- `trace(out_dir)`: context manager capturing a profiler session; view with
  TensorBoard's profile plugin or Perfetto (xplane → trace.json.gz is
  emitted automatically). Taken with the options the benchmark's traces
  use (`profile_options`): Python-call tracing off, host tracer level 2.

Host-side regions on the trace's timeline come from `telemetry.span()`,
the one probe: while a session is live every span is also a
`TraceAnnotation` named `<cat>/<name>`.

`trace` degrades to a no-op if the profiler backend is unavailable (e.g. a
second concurrent session), mirroring the monitoring subsystem's graceful
energy-meter fallback (reference monitoring.py:104-121).
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator, Optional

logger = logging.getLogger(__name__)


def profile_options():
    """The profiler options of every trace this repo takes: Python-call
    tracing off (with it on, a few seconds of serving are too much to read
    back; the program's host time is named by `telemetry.span`'s
    annotations instead), XLA's host events and TraceAnnotations kept."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def start_trace(out_dir: str) -> None:
    """Start a profiler session into `out_dir` with `profile_options()`.
    Raises what JAX raises: RuntimeError while another session is live."""
    import jax
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir, profiler_options=profile_options())


@contextlib.contextmanager
def trace(out_dir: Optional[str]) -> Iterator[None]:
    """Capture a JAX profiler trace into `out_dir` (no-op when None)."""
    if not out_dir:
        yield
        return
    import jax
    try:
        start_trace(out_dir)
    except Exception as exc:  # bad path / profiler busy: degrade gracefully
        logger.warning("trace capture unavailable (%s); continuing without",
                       exc)
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
            logger.info("trace written to %s (view: tensorboard --logdir %s)",
                        out_dir, out_dir)
        except Exception as exc:
            logger.warning("trace stop failed: %s", exc)
