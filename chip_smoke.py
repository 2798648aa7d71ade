#!/usr/bin/env python
"""The system's main path, once, on the chip: the quickest proof that the
program still starts there.

    python chip_smoke.py                # one chip, every phase
    python chip_smoke.py --four-chips   # the four-stage SPMD path and the
                                        # one-chip run it is compared with

Every phase is a child process that exits before the next one starts, because
a chip belongs to one process at a time: this parent never touches JAX. The
children are the CLIs as users start them (`save_model_weights.py`,
`runtime.py`, `tools/serve.py`, `tools/train.py`) at the full width of
ViT-Large and gpt2-medium, on weights made from a seed. The exception is the
`probe` phase, which is this file run again with `--child probe`: the device,
the fixed cost of a dispatch, whether `block_until_ready` fences, and the
repaired Pallas kernels and the short attention core against their XLA
references.

Each phase prints one JSON line. A phase that fails raises and the script
exits non-zero; nothing is caught and reported as a skip, and nothing carries
on on the CPU: a child that reports another platform than `tpu` is a failure.
The last line, printed only when every phase passed, is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

Work files (weights, logits, lowered programs) go to `.smoke/` and the
children's logs to `chiprun_out/smoke/`, both inside the checkout and both
git-ignored. Numbers printed here are one smoke reading each, not a benchmark.
"""
import argparse
import dataclasses
import glob
import json
import math
import os
import random
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".smoke")
LOGS = os.path.join(REPO, "chiprun_out", "smoke")

# the whole script must end inside 1200 s; a child is killed at this point
BUDGET_S = 1150.0

# Logits of two runs of one bf16 model agree to this share of the largest
# logit: 2**-5 is sixteen bf16 roundings (unit roundoff 2**-9) of headroom
# for a difference in fusion between the one-program and the staged forward.
BF16_LOGIT_TOLERANCE = 2.0 ** -5

# The short attention kernel's context agrees with the einsums' to this share
# of the context's range: a bfloat16 result is rounded to 2**-9 of its value,
# so two of them differ by 2**-9 of the range at its ends, and the float32
# ones by the two products' passes (my chip runs, PR 60, calls 189 and 203:
# at most 9.6e-4 and 2.5e-4). A scratch buffer whose store overtook a read
# shows as half the range or more (`ops/short_attention.py`, module
# docstring).
ATTENTION_TOLERANCE = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run. The defaults are the smoke itself; the CPU
    rehearsal test (tests/test_chip_smoke.py) shrinks them."""
    vit: str = "google/vit-large-patch16-224"
    two_stages: str = "1,48,49,96"
    spmd_stages: str = "1,24,25,48,49,72,73,96"     # one stage on each chip
    batch: int = 64
    ubatch: int = 8
    decoder: str = "gpt2-medium"
    vocab: int = 50257
    max_len: int = 1024
    prompt_len: int = 128
    new_tokens: int = 32
    train_batch: int = 8        # one chip's sizing (docs/TRAIN.md)
    train_ubatches: int = 4
    train_steps: int = 4
    edge_shape: tuple = (8, 197, 1024)      # the ViT-L stage edge
    matmul_mkn: tuple = (1576, 1024, 4096)  # 8 x 197 rows into the MLP
    fence_dim: int = 8192       # the fence check's matmul chain
    fence_chain: int = 16
    # the short attention core's calls (batch, positions, heads, head width,
    # type), each one `ops/short_attention.py::takes`: ViT-L's and DeiT-B's,
    # the longest row of ViT-L's width, one tile of keys with and without a
    # remainder, a single row, a head of 128, float32
    attention_calls: tuple = (
        (8, 197, 16, 64, "bfloat16"), (8, 198, 12, 64, "bfloat16"),
        (8, 256, 16, 64, "bfloat16"), (8, 129, 16, 64, "bfloat16"),
        (8, 128, 16, 64, "bfloat16"), (8, 50, 16, 64, "bfloat16"),
        (2, 1, 2, 64, "bfloat16"), (4, 197, 8, 128, "bfloat16"),
        (8, 197, 12, 64, "float32"))


class PhaseFailed(RuntimeError):
    """A phase did not do what it must; the script exits non-zero."""


def _check(condition, message):
    if not condition:
        raise PhaseFailed(message)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

class Child:
    """One child process: its merged output as (seconds since start, line)
    pairs, also kept in a log file. Killed at `timeout`."""

    def __init__(self, name, argv, timeout):
        os.makedirs(LOGS, exist_ok=True)
        os.makedirs(WORK, exist_ok=True)
        self.name = name
        self.ir_dir = os.path.join(WORK, "ir", name)
        shutil.rmtree(self.ir_dir, ignore_errors=True)
        env = dict(os.environ, PYTHONPATH=REPO, PYTHONUNBUFFERED="1",
                   # lowered programs land here, before any cache lookup:
                   # how the parent sees which kernels a child compiled
                   JAX_DUMP_IR_TO=self.ir_dir)
        self.lines = []
        self.log_path = os.path.join(LOGS, f"{name}.log")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable] + argv, cwd=WORK, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        self._killer = threading.Timer(timeout, self.proc.kill)
        self._killer.daemon = True
        self._killer.start()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        with open(self.log_path, "w", encoding="utf8") as log:
            for line in self.proc.stdout:
                self.lines.append((time.monotonic() - self.t0,
                                   line.rstrip("\n")))
                log.write(line)
                log.flush()

    def _exited(self, when):
        """The failure to raise for a child that ended when it must not."""
        tail = "\n".join(line for _, line in self.lines[-25:])
        return PhaseFailed(f"{self.name} exited {self.proc.returncode} "
                           f"{when} (log: {self.log_path}):\n{tail}")

    def wait(self):
        """Wait for the child's own end; raise unless it exited 0."""
        rc = self.proc.wait()
        self._reader.join(timeout=30)
        self._killer.cancel()
        wall = time.monotonic() - self.t0
        if rc != 0:
            raise self._exited(f"after {wall:.0f}s")
        return wall

    def stop(self):
        """End a child that serves until told to stop."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=30)
        self._killer.cancel()

    def find(self, pattern):
        """(seconds, match) of every line that matches `pattern`."""
        found = []
        for at, line in list(self.lines):
            match = re.search(pattern, line)
            if match:
                found.append((at, match))
        return found

    def await_line(self, pattern):
        """Block until a line matches `pattern`; raise if the child ends
        first. Returns the seconds at which the line came."""
        while not self.find(pattern):
            if self.proc.poll() is not None:
                self._reader.join(timeout=30)
                if self.find(pattern):
                    break
                raise self._exited(f"before /{pattern}/")
            time.sleep(0.1)
        return self.find(pattern)[0][0]

    def json_after(self, prefix):
        """The JSON value of the last `<prefix> {...}` line, or None."""
        found = self.find(re.escape(prefix) + r" (.*)$")
        return json.loads(found[-1][1].group(1)) if found else None

    def kernels(self):
        """Names of the Pallas kernels in the programs this child lowered,
        and how many programs it lowered."""
        names, programs = set(), 0
        for path in glob.glob(os.path.join(self.ir_dir, "*.mlir")):
            programs += 1
            with open(path, encoding="utf8", errors="replace") as mlir:
                text = mlir.read()
            if "@tpu_custom_call" in text:
                names.update(re.findall(r'kernel_name = "([^"]+)"', text))
        return sorted(names), programs


class Run:
    """One run of the script: what it must find, and how long it may take."""

    def __init__(self, sizes, platform, device_count):
        self.sizes = sizes
        self.platform = platform
        self.device_count = device_count
        self.deadline = time.monotonic() + BUDGET_S
        self.device = None      # as the first child reported it
        self.weights = None     # phase_weights: the seeded weights file
        self.reference = None   # phase_vit_one_stage: the one-stage logits

    def left(self):
        """Seconds this run may still take."""
        return max(self.deadline - time.monotonic(), 1.0)

    def child(self, name, argv):
        return Child(name, argv, self.left())

    def check_device(self, child):
        """The device a child reports must be the one this run is for:
        checked as soon as the child names it, so that a run without the
        chip ends at once."""
        try:
            child.await_line(r"^devices: ")
            device = child.json_after("devices:")
            _check(device["platform"] == self.platform
                   and device["count"] == self.device_count,
                   f"{child.name} ran on {device}, not on "
                   f"{self.device_count} {self.platform} device(s)")
        except PhaseFailed:
            child.stop()
            raise
        if self.device is None:
            self.device = device
        return device


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_probe(run):
    """The device, dispatch cost, fences and kernels (a child of this file)."""
    child = run.child("probe", [os.path.abspath(__file__), "--child", "probe",
                                "--sizes", json.dumps(
                                    dataclasses.asdict(run.sizes))])
    device = run.check_device(child)
    wall = child.wait()
    facts = child.json_after("probe:")
    _check(facts is not None, "probe printed no facts")
    if run.platform == "tpu":
        # on a TPU `auto` means the kernel: its custom call is in the
        # program the seam compiled, or the route is a hidden fallback
        for name, compiled in facts["kernel_in_program"].items():
            _check(compiled, f"no tpu_custom_call in the {name} program")
    return dict(device=device, wall_s=round(wall, 1), **facts)


def phase_devices(run):
    """The devices alone: the guard that fails at once without them."""
    child = run.child("devices", [os.path.abspath(__file__), "--child",
                                  "devices"])
    device = run.check_device(child)
    return dict(device=device, wall_s=round(child.wait(), 1))


def phase_weights(run):
    """One seeded weights file, so that every partition runs one model
    (the random-init fallback draws each shard from the start of the same
    stream, so two partitions of it are two different models)."""
    child = run.child("weights", [
        os.path.join(REPO, "save_model_weights.py"), "--random",
        "-m", run.sizes.vit, "-o", WORK])
    wall = child.wait()
    # a file left by an earlier run is kept: the seed makes it the same one
    named = child.find(r"(saving weights file|weights file already exists)"
                       r": (\S+)$")
    _check(named, "save_model_weights.py named no weights file")
    run.weights = named[-1][1].group(2)
    _check(os.path.exists(run.weights), f"no weights file at {run.weights}")
    return dict(wall_s=round(wall, 1),
                reused=named[-1][1].group(1) != "saving weights file",
                bytes=os.path.getsize(run.weights))


def _runtime(run, name, stages, extra):
    """One `runtime.py` run on the seeded inputs; logits saved for the
    comparisons. Returns the phase's facts and the logits."""
    sizes = run.sizes
    results = os.path.join(WORK, f"{name}.npz")
    if os.path.exists(results):
        os.remove(results)
    child = run.child(name, [
        os.path.join(REPO, "runtime.py"), "0", str(stages),
        "-m", sizes.vit, "-M", run.weights,
        "-b", str(sizes.batch), "-u", str(sizes.ubatch), "-t", "bfloat16",
        "--save-results", results] + extra)
    device = run.check_device(child)
    wall = child.wait()
    # e.g. `-c spmd` handing a partition it cannot express to the host driver
    _check(not child.find(r"falling back"),
           f"{name} fell back to another path (log: {child.log_path})")
    import ml_dtypes
    import numpy as np
    with np.load(results) as saved:
        logits = np.concatenate([saved[key] for key in saved.files])
    if logits.dtype.kind == "V":        # bf16 comes back as 2-byte voids
        logits = logits.view(ml_dtypes.bfloat16)
    logits = logits.astype(np.float32)
    _check(np.isfinite(logits).all(), f"{name}: logits are not finite")
    _check(logits.shape[0] >= sizes.batch and logits.ndim == 2,
           f"{name}: logits have shape {logits.shape}")
    kernels, programs = child.kernels()
    facts = dict(device=device, wall_s=round(wall, 1), kernels=kernels,
                 programs=programs, logits_shape=list(logits.shape),
                 device_memory=child.json_after("device_memory:"))
    rounds = child.find(r"^round=(\d+) latency_sec=([\d.]+) "
                        r"throughput_items_sec=([\d.]+)")
    if rounds:      # the host driver: round 0 compiles, the last is warm
        facts.update(cold_s=float(rounds[0][1].group(2)),
                     warm_s=float(rounds[-1][1].group(2)),
                     img_per_s=float(rounds[-1][1].group(3)))
    else:           # the spmd driver times its second, warm run only
        timed = child.find(r"^latency_sec=([\d.]+) "
                           r"throughput_items_sec=([\d.]+)")
        _check(timed, f"{name}: no latency line")
        started = child.find(r"^devices: ")[0][0]
        facts.update(cold_s=round(timed[-1][0] - started
                                  - float(timed[-1][1].group(1)), 3),
                     warm_s=float(timed[-1][1].group(1)),
                     img_per_s=float(timed[-1][1].group(2)))
    return facts, logits


def _agreement(reference, logits):
    import numpy as np
    n = min(len(reference), len(logits))
    reference, logits = reference[:n], logits[:n]
    return {
        "top1_agreement": round(float(np.mean(
            reference.argmax(-1) == logits.argmax(-1))), 4),
        "max_abs_logit_diff": float(np.abs(reference - logits).max()),
        "max_abs_logit": float(np.abs(reference).max()),
    }


def _check_close(name, agreement):
    bound = BF16_LOGIT_TOLERANCE * agreement["max_abs_logit"]
    _check(agreement["max_abs_logit_diff"] <= bound,
           f"{name}: logits differ from the one-stage run by "
           f"{agreement['max_abs_logit_diff']:.4g}, more than {bound:.4g}")


def phase_vit_one_stage(run):
    facts, run.reference = _runtime(run, "vit_one_stage", 1,
                                    ["--measure-rounds", "3"])
    return facts


def phase_vit_two_stages(run):
    """Two stages sharing the chip, unquantised: the same logits."""
    facts, logits = _runtime(run, "vit_two_stages", 2, [
        "-c", "host", "-pt", run.sizes.two_stages, "-q", "0,0",
        "--measure-rounds", "3"])
    facts.update(_agreement(run.reference, logits))
    _check_close("vit_two_stages", facts)
    return facts


def phase_vit_two_stages_q8(run):
    """The same over an 8-bit edge: the path that reaches the fused quant
    kernels. Agreement is reported, not gated: random-init margins are thin."""
    facts, logits = _runtime(run, "vit_two_stages_q8", 2, [
        "-c", "host", "-pt", run.sizes.two_stages, "-q", "8,0",
        "--measure-rounds", "3"])
    facts.update(_agreement(run.reference, logits))
    if run.platform == "tpu":
        for kernel in ("_encode_kernel", "_decode_kernel"):
            _check(kernel in facts["kernels"],
                   f"the 8-bit edge compiled without {kernel}: "
                   f"{facts['kernels']}")
    return facts


def phase_vit_spmd_stages(run):
    """One SPMD program, one stage on each chip: the same logits, and a
    comparable share of the bytes on every chip."""
    stages = run.device_count
    _check(run.sizes.spmd_stages.count(",") + 1 == 2 * stages,
           f"{run.sizes.spmd_stages} is not {stages} stages")
    facts, logits = _runtime(run, "vit_spmd_stages", stages, [
        "-c", "spmd", "-pt", run.sizes.spmd_stages])
    facts.update(_agreement(run.reference, logits))
    _check_close("vit_spmd_stages", facts)
    memory = facts["device_memory"]
    _check(len(memory) == stages,
           f"device_memory names {len(memory)} devices")
    if run.platform == "tpu":
        # bytes in use while the pipeline stands (peaks also count the
        # weights' staging through the first chip)
        in_use = [row["bytes_in_use"] for row in memory]
        _check(min(in_use) > 0.25 * max(in_use),
               f"the stages do not share the chips evenly: {in_use} bytes")
    return facts


def _post(url, body, timeout):
    request = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    tik = time.monotonic()
    with urllib.request.urlopen(request, timeout=timeout) as response:
        lines = [json.loads(line) for line in response.read().splitlines()
                 if line.strip()]
    return lines, time.monotonic() - tik


def phase_serve(run):
    """`tools/serve.py` answers /healthz and a handful of /generate
    requests, plain and streamed; both give the same tokens."""
    sizes = run.sizes
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    child = run.child("serve", [
        os.path.join(REPO, "tools", "serve.py"), "-m", sizes.decoder,
        "-t", "bfloat16", "--max-len", str(sizes.max_len),
        "--port", str(port)])
    try:
        device = run.check_device(child)
        ready_s = child.await_line(r"^serving ")
        with urllib.request.urlopen(f"{url}/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        _check(health.get("ok") is True, f"/healthz says {health}")

        rng = random.Random(0)
        prompts = [[rng.randrange(sizes.vocab)
                    for _ in range(sizes.prompt_len)] for _ in range(4)]
        seconds, answers = [], []
        for prompt in prompts:      # the first compiles: it is the cold one
            lines, took = _post(f"{url}/generate", {
                "ids": [prompt], "new_tokens": sizes.new_tokens}, run.left())
            ids = lines[0]["ids"][0]
            _check(len(ids) == sizes.prompt_len + sizes.new_tokens
                   and ids[:sizes.prompt_len] == prompt
                   and all(isinstance(t, int) and t >= 0 for t in ids),
                   f"/generate answered {lines[0]}")
            seconds.append(took)
            answers.append(ids)
        streamed, stream_s = _post(f"{url}/generate", {
            "ids": [prompts[-1]], "new_tokens": sizes.new_tokens,
            "stream": True}, run.left())
        final = streamed[-1]
        _check(final["ids"][0] == answers[-1],
               "the streamed request gave other tokens than the plain one")
        _check(len(streamed) - 1 == sizes.new_tokens == final["steps"],
               f"streamed {len(streamed) - 1} steps")
    finally:
        child.stop()
    kernels, programs = child.kernels()
    warm = statistics.median(seconds[1:])
    return dict(device=device, ready_s=round(ready_s, 1),
                cold_s=round(seconds[0], 3), warm_s=round(warm, 3),
                tok_per_s=round(sizes.new_tokens / warm, 1),
                stream_s=round(stream_s, 3),
                first_token_ms=final["first_token_ms"],
                stream_matches_plain=True, requests=len(prompts) + 1,
                kernels=kernels, programs=programs)


def phase_train(run):
    """`tools/train.py` takes a few steps; the loss is finite and falls."""
    sizes = run.sizes
    child = run.child("train", [
        os.path.join(REPO, "tools", "train.py"), "-m", sizes.vit,
        "-t", "bfloat16", "--remat", "-b", str(sizes.train_batch),
        "-u", str(sizes.train_ubatches), "--steps", str(sizes.train_steps)])
    device = run.check_device(child)
    wall = child.wait()
    steps = child.find(r"^step=(\d+) loss=(\S+)")
    losses = [float(match.group(2)) for _, match in steps]
    _check(len(losses) == sizes.train_steps,
           f"train.py logged {len(losses)} steps")
    _check(all(math.isfinite(loss) for loss in losses),
           f"the loss is not finite: {losses}")
    _check(losses[-1] < losses[0], f"the loss did not fall: {losses}")
    started = child.find(r"^devices: ")[0][0]
    gaps = [b[0] - a[0] for a, b in zip(steps, steps[1:])]
    warm = statistics.median(gaps)
    images = sizes.train_batch * sizes.train_ubatches
    kernels, programs = child.kernels()
    return dict(device=device, wall_s=round(wall, 1),
                cold_s=round(steps[0][0] - started, 3),
                warm_s=round(warm, 4), img_per_s=round(images / warm, 1),
                losses=losses, kernels=kernels, programs=programs,
                device_memory=child.json_after("device_memory:"))


ONE_CHIP = (phase_probe, phase_weights, phase_vit_one_stage,
            phase_vit_two_stages, phase_vit_two_stages_q8, phase_serve,
            phase_train)
FOUR_CHIPS = (phase_devices, phase_weights, phase_vit_one_stage,
              phase_vit_spmd_stages)


def run_phases(phases, sizes, platform, device_count):
    """Run the phases in order, one JSON line each; returns the device they
    ran on. The first phase that fails ends the run."""
    run = Run(sizes, platform, device_count)
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        try:
            facts = phase(run)
        except BaseException as failure:
            print(json.dumps({"phase": name, "ok": False,
                              "error": str(failure)[:2000]}), flush=True)
            raise
        print(json.dumps(dict(phase=name, ok=True, **facts)), flush=True)
    return run.device


# --------------------------------------------------------------------------
# the probe child: the only code of this file that touches JAX
# --------------------------------------------------------------------------

def _median_ms(fn, reps):
    samples = []
    for _ in range(reps):
        tik = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - tik)
    return statistics.median(samples) * 1e3


# where `kernel_checks.gelu` counts misses: |x| up to GELU_COUNTED_TO and a
# value a few binades over the least normal number (under it a backend may
# flush a part of the sum)
GELU_COUNTED_TO = 64.0
GELU_NO_FLUSH = 2.0 ** -120


def gelu_float64(x):
    """`0.5 x erfc(-x / sqrt 2)` of float64(x); -inf gives 0."""
    import numpy as np
    from scipy.special import erfc
    x = np.asarray(x).astype(np.float64)
    with np.errstate(invalid="ignore"):
        y = 0.5 * x * erfc(-x / math.sqrt(2.0))
    return np.where(np.isneginf(x), 0.0, y)


def spacing(y, dtype):
    """`dtype`'s ulp at |y|, a subnormal's being the least normal's."""
    import ml_dtypes
    import numpy as np
    info = ml_dtypes.finfo(dtype)
    exponent = np.floor(np.log2(np.maximum(np.abs(y), float(info.tiny))))
    return 2.0 ** (exponent - info.nmant)


def gelu_bfloat16_ulps(fn):
    """(x, the float64 GeLU of x, |fn(x) - it| in bfloat16 ulp, the ulp) at
    the 65,280 finite bfloat16 inputs."""
    import jax
    import ml_dtypes
    import numpy as np
    bf16 = ml_dtypes.bfloat16
    with np.errstate(invalid="ignore"):
        x = np.arange(65536, dtype=np.uint32).astype(np.uint16).view(
            bf16).astype(np.float64)
    x = x[np.isfinite(x)]
    want, got = gelu_float64(x), np.asarray(jax.jit(fn)(x.astype(bf16)))
    ulp = spacing(want, bf16)
    return x, want, np.abs(got.astype(np.float64) - want) / ulp, ulp


def gelu_float32_inputs():
    """ISSUE 61's grid of 4,096 points of [-12, 12], and 400,000 points of
    [-6.3, 6.3], where a float32 GeLU is above 2^-30."""
    import numpy as np
    return {"grid": np.linspace(-12, 12, 4096).astype(np.float32),
            "dense": np.random.default_rng(61).uniform(
                -6.3, 6.3, 400000).astype(np.float32)}


def gelu_float32_ulps(fn, x):
    """|fn(x) - GeLU(x)| in float32 ulp, an error under 2^-30 being none."""
    import jax
    import numpy as np
    want = gelu_float64(x)
    err = np.abs(np.asarray(jax.jit(fn)(x)).astype(np.float64) - want)
    return np.where(err <= 2.0 ** -30, 0.0, err / spacing(want, np.float32))


def exp_worst_ulp():
    """How far this backend's float32 `exp` is from float64's, in float32
    ulp, at the arguments the GeLU gives it (-a^2 / 2 up to a = 6.3): no
    float32 form that takes an `exp` is closer. 1 on the CPU, 63 on a v5e."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    a = gelu_float32_inputs()["dense"]
    v = a * a * np.float32(-0.5)
    want = np.exp(v.astype(np.float64))
    got = np.asarray(jax.jit(jnp.exp)(v)).astype(np.float64)
    return float((np.abs(got - want) / spacing(want, np.float32)).max())


def gelu_miss_counts(fn):
    """What `kernel_checks.gelu` reports of one form on this backend."""
    import numpy as np
    float32 = {name: gelu_float32_ulps(fn, x)
               for name, x in gelu_float32_inputs().items()}
    x, want, ulps, ulp = gelu_bfloat16_ulps(fn)
    whole = np.abs(want) >= GELU_NO_FLUSH
    counted = whole & (np.abs(x) <= GELU_COUNTED_TO)
    return {"counted": int(counted.sum()),
            "past_half_an_ulp": int((ulps[counted] > 0.5).sum()),
            "past_an_ulp": int((ulps[counted] > 1).sum()),
            "worst_ulp": float(ulps[whole].max()),
            "past_an_ulp_or_2^-24": int(
                (ulps > np.maximum(1.0, 2.0 ** -24 / ulp)).sum()),
            "float32_worst_ulp": {name: float(e.max())
                                  for name, e in float32.items()},
            "float32_past_4_ulp": {name: int((e > 4).sum())
                                   for name, e in float32.items()}}


def child_probe(sizes):
    from pipeedge_tpu.utils import enable_compile_cache, report_devices
    enable_compile_cache()
    report_devices()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.models import layers
    from pipeedge_tpu.ops import (fused_quant, int8_matmul, quant,
                                  short_attention)

    facts = {}
    # the fixed cost of one small dispatch, fenced each way
    x = jnp.ones((8, 128), jnp.float32)
    bump = jax.jit(lambda v: v + 1)
    total = jax.jit(jnp.sum)
    bump(x).block_until_ready()
    float(total(x))
    facts["dispatch_ms"] = _median_ms(
        lambda: bump(x).block_until_ready(), 200)
    facts["dispatch_readback_ms"] = _median_ms(lambda: float(total(x)), 200)

    # does block_until_ready fence as well as a scalar read-back? A chain of
    # matmuls long enough to dwarf a dispatch, fenced each way; if
    # block_until_ready returned early, the read-back after it would pay
    # the chain's time
    dim, chain = sizes.fence_dim, sizes.fence_chain
    a = jnp.ones((dim, dim), jnp.bfloat16)

    @jax.jit
    def matmul_chain(m):
        def step(c, _):
            y = jnp.dot(c, m, preferred_element_type=jnp.float32)
            return (y * 1e-4).astype(jnp.bfloat16), None
        return jax.lax.scan(step, m, None, length=chain)[0]

    float(matmul_chain(a)[0, 0])
    blocked, after, scalar = [], [], []
    for _ in range(5):
        tik = time.perf_counter()
        y = matmul_chain(a)
        y.block_until_ready()
        blocked.append(time.perf_counter() - tik)
        tik = time.perf_counter()
        float(y[0, 0])
        after.append(time.perf_counter() - tik)
        tik = time.perf_counter()
        float(matmul_chain(a)[0, 0])
        scalar.append(time.perf_counter() - tik)
    blocked_s, scalar_s = statistics.median(blocked), statistics.median(scalar)
    facts.update(
        block_until_ready_ms=blocked_s * 1e3,
        readback_after_block_ms=statistics.median(after) * 1e3,
        scalar_readback_ms=scalar_s * 1e3,
        block_until_ready_fences=bool(blocked_s >= 0.9 * scalar_s),
        matmul_chain_tflops=2 * chain * dim ** 3 / blocked_s / 1e12)

    # the repaired kernels through their dispatch seams, against the XLA ops
    rng = np.random.default_rng(0)
    edge = jnp.asarray(rng.normal(size=sizes.edge_shape) * 3.7 - 1.2,
                       jnp.float32)
    in_program, kernel_checks = {}, {}
    for bit in (8, 4):
        encode = jax.jit(lambda v, bit=bit: fused_quant.encode_outerdim(v, bit))
        decode = jax.jit(fused_quant.decode_outerdim)
        enc = encode(edge)
        in_program[f"fused_encode_{bit}"] = \
            "tpu_custom_call" in encode.lower(edge).compile().as_text()
        in_program[f"fused_decode_{bit}"] = \
            "tpu_custom_call" in decode.lower(enc).compile().as_text()
        ref = quant.tensor_encode_outerdim(edge, bit)
        dec, ref_dec = decode(enc), quant.tensor_decode_outerdim(ref)
        step = float(jnp.max(ref.scale)) / ((1 << bit) - 1)
        kernel_checks[f"fused_quant_{bit}"] = {
            "words_differ": int(jnp.sum(enc.data != ref.data)),
            "words": int(enc.data.size),
            "scale_shift_equal": bool(jnp.all(enc.scale == ref.scale)
                                      & jnp.all(enc.shift == ref.shift)),
            "decode_max_abs_diff": float(jnp.max(jnp.abs(dec - ref_dec))),
            "quant_step": step,
        }
        _check(kernel_checks[f"fused_quant_{bit}"]["scale_shift_equal"]
               and kernel_checks[f"fused_quant_{bit}"]["decode_max_abs_diff"]
               <= 1.001 * step,
               f"fused quant bit={bit} is more than one level from the "
               f"XLA ops: {kernel_checks[f'fused_quant_{bit}']}")
    m, k, n = sizes.matmul_mkn
    x_q, x_s = int8_matmul.quantize_act_blocks(
        jnp.asarray(rng.normal(size=(m, k)), jnp.float32), 128)
    w_q, w_s = int8_matmul.quantize_weight(
        jnp.asarray(rng.normal(size=(k, n)), jnp.float32))
    matmul = jax.jit(lambda *ops: int8_matmul.matmul_q(*ops, 128))
    in_program["int8_matmul"] = "tpu_custom_call" in \
        matmul.lower(x_q, x_s, w_q, w_s).compile().as_text()
    got = matmul(x_q, x_s, w_q, w_s)
    ref = int8_matmul.matmul_xla(x_q, x_s, w_q, w_s, 128)
    rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    kernel_checks["int8_matmul"] = {"max_rel_diff": rel}
    _check(rel <= 1e-4, f"int8 matmul is {rel} from matmul_xla")
    # the short attention core against the einsum core, at every kind of
    # call `takes` admits: Mosaic's ordering of scratch accesses is what
    # interpret mode cannot show, so on the CPU this only rehearses
    interpret = jax.default_backend() != "tpu"
    gaps = {}
    for b, s, h, hd, dtype in sizes.attention_calls:
        _check(short_attention.takes(s, h * hd, hd, jnp.dtype(dtype).itemsize),
               f"the short core does not take {(b, s, h, hd, dtype)}")
        q, k, v = (jnp.asarray(rng.normal(size=(b, s, h * hd)), dtype)
                   for _ in range(3))
        got = jax.jit(lambda q, k, v, h=h: short_attention.short_attention(
            q, k, v, h, layers.einsum_core, interpret))(q, k, v)
        want = jax.jit(lambda q, k, v, split=(b, s, h, hd): layers.einsum_core(
            q.reshape(split), k.reshape(split), v.reshape(split)
        ).reshape(q.shape))(q, k, v)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        gap = float(np.abs(got - want).max() / (want.max() - want.min()))
        gaps[f"{b}x{s}x{h}x{hd}_{dtype}"] = gap
        _check(np.isfinite(got).all() and gap <= ATTENTION_TOLERANCE,
               f"the short attention core is {gap} of the range from the "
               f"einsums at {(b, s, h, hd, dtype)}")
    kernel_checks["short_attention"] = {"gap_of_range": gaps}
    # the erf GeLU at every bfloat16 input and on two float32 samples against
    # float64, beside the form it replaced: what this backend's `exp`, divide
    # and fusion make of each
    kernel_checks["gelu"] = {
        name: gelu_miss_counts(fn) for name, fn in (
            ("shipped", layers.gelu),
            ("jax_nn_gelu", lambda v: jax.nn.gelu(v, approximate=False)))}
    kernel_checks["gelu"]["exp_worst_ulp"] = exp_worst_ulp()
    shipped, before = (kernel_checks["gelu"][name]
                       for name in ("shipped", "jax_nn_gelu"))
    # float32: the form's own roundings are 5 ulp; the rest is the
    # backend's `exp` (on a v5e `jax.nn.gelu`, whose `erfc` takes no `exp`
    # for |x| < 1, is closer there: PERF.md section 7, row 47)
    _check(shipped["past_an_ulp_or_2^-24"] == 0
           and all(shipped[key] <= before[key] for key in
                   ("past_half_an_ulp", "past_an_ulp", "worst_ulp"))
           and max(shipped["float32_worst_ulp"].values())
           <= kernel_checks["gelu"]["exp_worst_ulp"] + 6,
           f"the erf GeLU misses the float64 one: {kernel_checks['gelu']}")
    facts.update(kernel_in_program=in_program, kernel_checks=kernel_checks)
    print(f"probe: {json.dumps(facts)}", flush=True)


def child_devices():
    from pipeedge_tpu.utils import report_devices
    report_devices()


def _as_tuple(value):
    """A field of `Sizes` as JSON brought it: lists are its tuples."""
    return tuple(map(_as_tuple, value)) if isinstance(value, list) else value


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the four-stage SPMD path and the one-chip "
                             "run it is compared with, and no other phase")
    parser.add_argument("--child", choices=["probe", "devices"],
                        help=argparse.SUPPRESS)
    parser.add_argument("--sizes", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child == "probe":
        fields = json.loads(args.sizes)
        child_probe(Sizes(**{key: _as_tuple(value)
                             for key, value in fields.items()}))
        return 0
    if args.child == "devices":
        child_devices()
        return 0
    phases, count = (FOUR_CHIPS, 4) if args.four_chips else (ONE_CHIP, 1)
    device = run_phases(phases, Sizes(), "tpu", count)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
