"""Utility modules: thread primitives, controllers, quantization policies, data."""
import json
import os

# the persistent compile cache's home when the environment names none: one
# fixed, git-ignored directory at the root of the checkout
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Keep compiled programs from one process to the next.

    Every CLI calls this before its first compile. Where
    `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its persistent
    cache there and this changes nothing; otherwise the cache goes to
    `COMPILE_CACHE_DIR`. The path is fixed because a cache that moves is
    never found again: it is not built from a temporary name, a pid or the
    time.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def report_devices() -> dict:
    """Print and return the devices this process runs on, as JAX reports
    them — the line by which a parent that stays off JAX (chip_smoke.py)
    learns what its child ran on. Initializes the backend."""
    import jax
    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    print(f"devices: {json.dumps(stamp)}", flush=True)
    return stamp


def report_device_memory() -> list:
    """Print and return the bytes in use, now and at their peak, on each
    device of this process (null where the backend keeps no count, as the
    CPU's does) — what shows a pipeline's stages all landing on one chip."""
    import jax
    rows = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        rows.append({"id": device.id,
                     "bytes_in_use": stats.get("bytes_in_use"),
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    print(f"device_memory: {json.dumps(rows)}", flush=True)
    return rows


def force_host_cpu_devices(n: int) -> None:
    """Point jax at >= n virtual CPU devices (for multi-"chip" testing
    without TPU hardware, SURVEY.md §4).

    Must run before the first backend initialization in the process:
    --xla_force_host_platform_device_count is parse-once. Safe to call
    multiple times; a too-small inherited device count is rewritten.
    """
    import re

    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      flags)
    if match is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(match.group(1)) < n:
        os.environ["XLA_FLAGS"] = (
            flags[:match.start()]
            + f"--xla_force_host_platform_device_count={n}"
            + flags[match.end():])
    if jax.config.jax_platforms != "cpu":
        jax.config.update("jax_platforms", "cpu")
