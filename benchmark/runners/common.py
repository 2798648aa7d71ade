"""What every runner shares: the run's context, the outcome it returns,
the compile cache, seeded keys and the profiler bracket."""
import dataclasses
import os
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(REPO, ".jax_cache")


@dataclasses.dataclass
class Context:
    cell: dict              # the entry of BENCHMARK.json's `workloads`
    config: dict            # benchmark/configs/<config>.json
    traffic: dict           # benchmark/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    work: str               # scratch directory of this cell, git-ignored
    started: float          # time.monotonic() when the process started
    platforms: tuple = ("tpu",)     # widened by the CPU rehearsal tests only


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    device: dict            # platform, kind, count, memory_peak_bytes
    end_to_end: dict        # metric name -> value, `setup_s` among them
    observed: dict          # what the per-layer readers read
    notes: dict = dataclasses.field(default_factory=dict)   # earlier lines


class Marks:
    """Seconds since the process started at which each phase of set-up
    ended: where `setup_s` goes, for an earlier line of the output."""

    def __init__(self, started):
        self.started = started
        self.at = {}

    def __call__(self, phase):
        self.at[phase] = round(time.monotonic() - self.started, 3)


def cache_environment(env):
    """The child's environment with the persistent compile cache at its
    fixed place inside the checkout (or where the caller's environment
    says) and every program admitted to it, however quick its compile."""
    env = dict(env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return env


def enable_cache():
    """The same, for a runner that holds the chip itself."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def seeded_key(seed):
    """A PRNG key from any whole number up to 2**63."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def start_trace(trace_dir):
    """Start the profiler with Python-call tracing off: the trace keeps
    device operations, XLA's host events and TraceAnnotations, and stays
    small enough to read back."""
    import jax
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def traced_window(trace_dir, seconds, body):
    """Run `body()` repeatedly for about `seconds` inside one profiler
    session, under the host annotation the reduction takes as its window.
    Returns the number of calls made."""
    import jax
    from benchmark import xplane
    start_trace(trace_dir)
    calls = 0
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            until = time.monotonic() + seconds
            while time.monotonic() < until:
                body()
                calls += 1
    finally:
        jax.profiler.stop_trace()
    return calls
