"""Served generation under an open loop: `tools/serve.py` as a child (through
`benchmark/serve_launcher.py`, which adds a side door for memory counters
and a profiler bracket and nothing else), driven over HTTP by
`benchmark/loadgen.py` from this parent, which stays off JAX until the
child has exited and only then runs the reference check on the chip.

The traffic file gives the server's arguments, the arrival rate and process,
and the prompt and answer lengths. Every prompt length the schedule uses is
sent once before the window (with enough tokens to cross into the next
attention bucket), so that nothing compiles inside it."""
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmark import correct, device, loadgen, weights, xplane
from benchmark.runners import common

HOST = "127.0.0.1"


class Server:
    """The child: its output as lines, and its side door."""

    def __init__(self, argv, cwd, env, log_path):
        self.lines = []
        self.log_path = log_path
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, text=True, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        with open(self.log_path, "w", encoding="utf8") as log:
            for line in self.proc.stdout:
                self.lines.append(line.rstrip("\n"))
                log.write(line)
                log.flush()

    def await_line(self, pattern, timeout):
        """The first line matching `pattern`; raises if the child ends or
        `timeout` seconds pass first."""
        until = time.monotonic() + timeout
        seen = 0
        while True:
            lines = list(self.lines)
            for line in lines[seen:]:
                match = re.search(pattern, line)
                if match:
                    return match
            seen = len(lines)
            if self.proc.poll() is not None or time.monotonic() > until:
                tail = "\n".join(self.lines[-20:])
                raise RuntimeError(
                    f"server gave no /{pattern}/ (exit "
                    f"{self.proc.poll()}, log {self.log_path}):\n{tail}")
            time.sleep(0.05)

    def ask(self, command, key, timeout):
        """Send a side-door command and return its answer's `key`."""
        before = len(self.lines)
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        until = time.monotonic() + timeout
        while time.monotonic() < until and self.proc.poll() is None:
            for line in list(self.lines)[before:]:
                if line.startswith("bench: "):
                    answer = json.loads(line[len("bench: "):])
                    if "error" in answer:
                        raise RuntimeError(f"{command}: {answer['error']}")
                    if key in answer:
                        return answer[key]
            time.sleep(0.05)
        raise RuntimeError(f"no answer to {command!r} from the server")

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=30)


def _get(port, path, timeout=30):
    with urllib.request.urlopen(f"http://{HOST}:{port}{path}",
                                timeout=timeout) as response:
        return response.read().decode("utf8")


def _free_port():
    with socket.socket() as probe:
        probe.bind((HOST, 0))
        return probe.getsockname()[1]


def _warm(port, traffic, vocabulary, seed):
    """One request of every prompt length, one after another; then wait
    until the server's brownout governor, which the slow compiling requests
    may have tripped, is back at its normal level."""
    rng = np.random.Generator(np.random.PCG64(seed))
    lengths = traffic["prompt_len"]["choices"]
    requests = [loadgen.Request(
        index=i, due_s=0.0,
        ids=[int(t) for t in rng.integers(0, vocabulary, size=length)],
        new_tokens=traffic.get("warm_new_tokens", 8))
        for i, length in enumerate(lengths)]
    for request in requests:
        loadgen.drive(HOST, port, [request], timeout=600.0)
        if not request.ok:
            raise RuntimeError(f"warm-up request of {len(request.ids)} "
                               f"tokens failed: {request.status} "
                               f"{request.error}")
    until = time.monotonic() + 60
    while time.monotonic() < until:
        health = json.loads(_get(port, "/healthz"))
        brownout = health.get("serving", {}).get("brownout") or {}
        if not brownout.get("level"):
            return
        time.sleep(0.25)
    raise RuntimeError("the server's brownout level did not return to 0")


def launch(ctx):
    """Write the seeded weights, start the server and warm it up. Returns
    (server, port, weights path, device stamp); the caller stops it."""
    config, traffic = ctx.config, ctx.traffic
    mark = common.Marks(ctx.started)
    os.makedirs(ctx.work, exist_ok=True)
    cwd = os.path.join(ctx.work, "serve")
    # the server takes no weights-file argument: it looks for the model's
    # default file name in its working directory
    path = weights.write(config, ctx.seed,
                         os.path.join(cwd, traffic["weights_file"]))
    mark("weights_file")
    port = _free_port()
    env = common.cache_environment(os.environ)
    env.update(PYTHONPATH=common.REPO, PYTHONUNBUFFERED="1")
    if ctx.trace:
        env["PIPEEDGE_SPAN_CAPACITY"] = str(1 << 20)
    argv = [sys.executable,
            os.path.join(common.REPO, "benchmark", "serve_launcher.py"),
            "-m", config["program_model"], "-t", config["dtype"],
            "--port", str(port)] \
        + [str(word) for word in traffic["server_args"]]
    server = Server(argv, cwd, env, os.path.join(ctx.work, "server.log"))
    try:
        stamp = json.loads(server.await_line(r"^devices: (.*)$", 300).group(1))
        device.require(stamp, ctx.cell["chips"], ctx.platforms)
        mark("server_devices")
        server.await_line(r"^serving ", 900)
        mark("server_ready")
        _warm(port, traffic, config["vocab_size"], ctx.seed)
        mark("warm_requests")
    except BaseException:
        server.stop()
        raise
    return server, port, path, mark.at


def run(ctx):
    config, traffic = ctx.config, ctx.traffic
    server, port, path, marks = launch(ctx)
    observed = {"config": config, "traffic": traffic}
    try:
        seconds = ctx.seconds if not ctx.trace \
            else min(ctx.seconds, traffic.get("layer_seconds", 20.0))
        requests = loadgen.schedule(traffic, config["vocab_size"], seconds,
                                    ctx.seed)
        if ctx.trace:
            _get(port, "/debug/spans")      # drop the warm-up's spans
        metrics_before = _get(port, "/metrics")
        first = time.monotonic()
        setup_s = first - ctx.started
        took = loadgen.drive(HOST, port, requests)
        metrics_after = _get(port, "/metrics")
        summary = loadgen.summarize(requests)
        observed.update(window_s=took, summary=summary,
                        metrics_before=metrics_before,
                        metrics_after=metrics_after)
        if ctx.trace:
            spans = json.loads(_get(port, "/debug/spans"))
            observed["spans"] = spans["spans"]
            observed["spans_dropped"] = spans["dropped"]
            # the profiler bracket, under the same load: a second, short
            # schedule with the trace taken from its middle
            trace_dir = os.path.join(ctx.work, "trace")
            trace_s = traffic.get("trace_seconds", 3.0)
            again = loadgen.schedule(traffic, config["vocab_size"],
                                     trace_s + 4.0, ctx.seed + 1)
            load = threading.Thread(target=loadgen.drive,
                                    args=(HOST, port, again), daemon=True)
            load.start()
            time.sleep(2.0)
            server.ask(f"trace {trace_dir} {trace_s}", "trace",
                       trace_s + 120)
            load.join(timeout=300)
            observed["trace"] = xplane.reduce_dir(trace_dir)
        stats = server.ask("stats", "stats", 60)
    finally:
        server.stop()

    # correctness, with the chip free again: a few answers teacher-forced
    # through the float32 reference, padded to one length so that one
    # program serves them all
    good = [r for r in requests if r.ok][:traffic.get("check_answers", 3)]
    ok, facts = False, {"tokens_checked": 0}
    if good:
        common.enable_cache()
        with np.load(path) as tensors:
            ok, facts = correct.tokens_near_greedy(
                config, tensors, [r.answer for r in good],
                [len(r.ids) for r in good], pad_to=traffic["check_pad_to"])
    os.remove(path)
    end_to_end = {"setup_s": setup_s,
                  "ttft_p95_ms": summary["ttft_p95_ms"],
                  "itl_p95_ms": summary["itl_p95_ms"],
                  "served_tok_per_s": summary["tokens"] / took}
    notes = {key: summary[key] for key in (
        "sent", "succeeded", "failed", "tokens", "ttft_p50_ms",
        "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms", "gen_late_p95_ms",
        "failures")}
    notes.update(reference=facts, window_s=took, setup_marks=marks,
                 ttft_samples=len(summary["ttft_ms"]),
                 itl_samples=len(summary["itl_ms"]))
    return common.Outcome(
        correct=ok, attempted=len(requests),
        failed=summary["failed"], device=stats, end_to_end=end_to_end,
        observed=observed, notes=notes)
