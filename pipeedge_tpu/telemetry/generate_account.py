"""The account a call of `DecodePipeline.generate` keeps of its batch.

`tok_per_s` of an offline cell blends a prompt pass and hundreds of steps,
and the spans of PR 27 (`generate/prefill|step|pick`) time the host's
dispatch, which ends long before the device does. This is the batch's own
account, always on, made of clock readings and non-blocking readiness
probes: nothing here fences inside a batch, dispatches, or reads an option.

- **Spans.** `BatchAccount` is the batch's `generate/batch` span and hands
  out its phases (`account.span("alloc"|"prefill"|"step"|"pick"|"finish"|
  "wait")`), each `telemetry.sunk_span`: the one probe with its two sinks
  (the ring, a live profiler session's `TraceAnnotation`), whose two clock
  readings always go to the account as well, as `telemetry.startup()`'s go
  to its counter. A phase is a call into the runtime (a dispatch or a
  wait): its seconds are the host's time *inside* calls; what is left of an
  interval is the time *between* calls: the interpreter, its garbage
  collector, a thread that was not running.
- **Marks.** About sixteen of the picked tokens and at most sixteen of a
  spanned prompt's outputs are kept as marks of the device's progress. At
  each dispatch the oldest unreached mark is asked `is_ready()`; when it
  first says yes the host clock is stamped and the array let go. Once all
  is dispatched the host waits on the rest in order (`generate/wait`). The
  first token's mark is the prompt's end on the device, the last token's
  the steps'; what follows it up to the batch's end is the *tail* (the
  counts read back, the result handed over), a last interval that is
  judged as a step is.
- **Stalls.** `find_stalls` is the rule, a pure function of the calls'
  starts and ends and the marks' times (`intervals`).
- **Where it goes.** The five `pipeedge_generate_*` families below, each
  read by a `*.generate` metric of the benchmark, steady batches only; the
  pipeline's `batch_accounts` (the last 64, plain dicts, whatever their
  kind); one `logger.warning` a stalled batch. docs/OBSERVABILITY.md has
  the reference.
"""
from __future__ import annotations

import bisect
import gc
import itertools
import json
import logging
import operator
import time
from typing import List, Sequence, Tuple

from . import _live_annotation, metrics, span, sunk_span

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):       # not Linux: no switches counted
    resource = None

logger = logging.getLogger(__name__)

HOST_PHASES = ("alloc", "prefill", "step", "pick", "finish", "wait")
ACCOUNTS_KEPT = 64
MARKS = 16
# an interval is a stall where a unit of it (a step, a span) took more than
# this many times the slower of its two neighbours'; a batch is warned of
# where its stalls' excess is more than this share of it
STALL_RATIO = 1.5
WARN_SHARE = 0.03
# what an interval's units are, by its phase: intervals are neighbours, and
# held to one another, where they count the same thing. The tail is one
# unit, held to the steps before it
UNITS = {"prompt": "spans", "decode": "steps", "tail": "steps"}

M_SECONDS = metrics.REGISTRY.counter(
    "pipeedge_generate_seconds_total",
    "seconds of steady batches on the device's progress marks: prompt "
    "(batch start to first token ready), decode (first token ready to the "
    "batch's end: the steps and the tail after the last token)")
M_POSITIONS = metrics.REGISTRY.counter(
    "pipeedge_generate_positions_total",
    "rows x positions of the prompts of steady batches")
M_STEPS = metrics.REGISTRY.counter(
    "pipeedge_generate_steps_total",
    "decode steps of steady batches, whatever the rows")
M_HOST_CPU = metrics.REGISTRY.counter(
    "pipeedge_generate_host_cpu_seconds_total",
    "CPU seconds of the generating thread over steady batches")
M_STALL_SECONDS = metrics.REGISTRY.counter(
    "pipeedge_generate_stall_seconds_total",
    "excess seconds of the stalled intervals of steady batches, by the "
    "side the host's time between calls puts them on")
for _phase in ("prompt", "decode"):
    M_SECONDS.declare(phase=_phase)
for _side in ("host", "device"):
    M_STALL_SECONDS.declare(side=_side)
M_POSITIONS.declare(phase="prompt")
M_STEPS.declare()
M_HOST_CPU.declare()

# nanoseconds the collector has run in this process, and the start of the
# collection that is open
_gc_ns = [0, 0]


def _on_gc(phase, _info):
    if phase == "start":
        _gc_ns[1] = time.monotonic_ns()
    elif _gc_ns[1]:
        _gc_ns[0] += time.monotonic_ns() - _gc_ns[1]
        _gc_ns[1] = 0


gc.callbacks.append(_on_gc)


def _switches() -> int:
    """Involuntary context switches of the calling thread so far."""
    if resource is None:
        return 0
    return resource.getrusage(_RUSAGE_THREAD).ru_nivcsw


def _builds() -> int:
    """Steps of builds of the pipeline's own programs so far."""
    return sum(count for count, _ in metrics.program_builds().values())


# -- the rule ------------------------------------------------------------

def intervals(start: float, calls: Sequence[Tuple[float, float]],
              marks: Sequence[Tuple[str, int, float]]) -> List[dict]:
    """The stretches between a batch's marks, with what the host did in
    each. `start` is the batch's start, `calls` the (start, end) of the
    host's calls into the runtime in order, `marks` the (phase, at, time)
    of the device's progress marks in order: `phase` is a key of `UNITS`,
    `at` how many units of it (spans; steps) were done at the mark. All
    times are on one clock, in one unit. An interval: `phase`, `at` (its
    end mark's), `units`, `seconds`, and `between`, the seconds of it that
    lie inside no call."""
    starts, ends = zip(*calls) if calls else ((), ())
    return _intervals(start, starts, ends, marks)


def _intervals(start, starts, ends, marks):
    spent = list(itertools.accumulate(map(operator.sub, ends, starts)))

    def inside(t):
        """Of the calls' time, what lies before `t`."""
        k = bisect.bisect_right(starts, t)
        if not k:
            return 0
        return spent[k - 1] - max(ends[k - 1] - t, 0)

    rows, prev_t, before, done = [], start, inside(start), {}
    for phase, at, t in marks:
        seconds, upto = t - prev_t, inside(t)
        rows.append({"phase": phase, "at": at,
                     "units": max(at - done.get(phase, 0), 1),
                     "seconds": seconds,
                     "between": max(seconds - (upto - before), 0)})
        prev_t, before, done[phase] = t, upto, at
    return rows


def find_stalls(rows: Sequence[dict]) -> List[dict]:
    """The stalled ones of `intervals`' rows. An interval whose seconds a
    unit are more than `STALL_RATIO` times the larger of its two
    neighbours' (of its own units: a span is held to spans, a step and the
    tail to steps) counts its `excess` over that neighbour. `side` is
    `host` where the seconds the host was not inside a call (its seconds
    between calls, and the collector's, `gc`, where a row has them: a
    collection inside a call stops the thread all the same) exceed its
    neighbours', at their rate a unit, by at least half the excess, else
    `device`: the host sat inside a call meanwhile, so the device or the
    runtime was late. An interval with no neighbour of its units (a prompt
    that is one program) cannot be judged."""
    def own(row):
        return row["between"] + row.get("gc", 0.0)

    found = []
    for i, row in enumerate(rows):
        near = [rows[j] for j in (i - 1, i + 1) if 0 <= j < len(rows)
                and UNITS[rows[j]["phase"]] == UNITS[row["phase"]]]
        if not near:
            continue
        units = row["units"]
        usual = max(n["seconds"] / n["units"] for n in near)
        if row["seconds"] / units <= STALL_RATIO * usual:
            continue
        excess = row["seconds"] - usual * units
        idle = own(row) - units * max(own(n) / n["units"] for n in near)
        found.append(dict(row, index=i, excess=excess,
                          side="host" if idle >= excess / 2 else "device"))
    return found


# -- the account ---------------------------------------------------------

class _Mark:
    """A dispatched array kept to see the device reach it: `phase` and
    `at` as `intervals` takes them, `sent` the ordinal of its dispatch;
    once reached (`array` let go) the host's clock then (None where a later
    mark was found ready at the same look), the host's `lead` (spans and
    steps dispatched since its own), and the collector's nanoseconds and
    the thread's involuntary switches so far."""

    __slots__ = ("phase", "at", "array", "sent", "time", "lead", "gc",
                 "switches")

    def __init__(self, phase, at, array, sent):
        self.phase, self.at, self.array, self.sent = phase, at, array, sent


class _NoAccount:
    """What `_prefill` is handed by a caller that keeps no account (the
    beam search, a prefix): its spans are the plain probe's, nothing is
    marked."""

    @staticmethod
    def span(phase: str):
        return span("generate", phase)

    def expect_spans(self, spans: int) -> None:
        pass

    def span_out(self, out) -> None:
        pass


NO_ACCOUNT = _NoAccount()


class BatchAccount:
    """The `generate/batch` span of one call of `generate`, and the account
    it settles as it ends: `with BatchAccount(...) as account:` around the
    batch, `account.span(phase)` around each call into the runtime,
    `account.span_out(out)` / `account.token(token)` after each dispatch of
    the prompt's spans and of the picks, `account.wait()` once everything
    is dispatched. A batch that raises settles nothing."""

    def __init__(self, kept, rows: int, prompt_positions: int,
                 new_tokens: int):
        self._kept = kept
        self._rows, self._positions = rows, prompt_positions
        self._new_tokens = new_tokens
        self._stride = max(1, (new_tokens - 1) // MARKS)
        self._spans, self._span_stride = 1, 1
        self._sent = self._spans_out = self._tokens_out = 0
        self._host = dict.fromkeys(HOST_PHASES, 0)
        # starts and ends of the calls, flat: ints alone, which the
        # collector does not track
        self._calls: List[int] = []
        self._marks: List[_Mark] = []
        self._next = self._first = 0    # the oldest unreached; token 0's
        self._now = 0                   # when the last call returned
        self._dispatched = None         # when the last dispatch returned

    def __enter__(self):
        self._traced = _live_annotation() is not None
        self._built = _builds()
        self._gc0, self._cpu0 = _gc_ns[0], time.thread_time_ns()
        self._switches0 = _switches()
        self._batch = sunk_span("generate", "batch", self._settle)
        self._batch.__enter__()
        return self

    def __exit__(self, exc_type, *exc):
        self._whole = exc_type is None and self._dispatched is not None
        return self._batch.__exit__(exc_type, *exc)

    def span(self, phase: str):
        """The span `generate/<phase>` (one of `HOST_PHASES`)."""
        return sunk_span("generate", phase, self._add)

    def _add(self, phase: str, t0: int, t1: int) -> None:
        self._host[phase] += t1 - t0
        self._calls += (t0, t1)
        self._now = t1

    def expect_spans(self, spans: int) -> None:
        """The prompt goes out in `spans` programs, each with an output."""
        self._spans = spans
        self._span_stride = -(-spans // MARKS)

    def span_out(self, out) -> None:
        """A span of the prompt is dispatched; `out` is its output."""
        self._sent += 1
        self._spans_out = done = self._spans_out + 1
        # the last span's mark is the first token
        if done % self._span_stride == 0 and done < self._spans:
            self._marks.append(_Mark("prompt", done, out, self._sent))
        self._look()

    def token(self, token) -> None:
        """A token is picked (dispatched, not read)."""
        self._sent += 1
        index = self._tokens_out
        self._tokens_out = index + 1
        if index == 0:
            self._first = len(self._marks)
            self._marks.append(_Mark("prompt", self._spans, token,
                                     self._sent))
        elif index % self._stride == 0 or index == self._new_tokens - 1:
            self._marks.append(_Mark("decode", index, token, self._sent))
        self._look()

    def _look(self) -> None:
        """Ask the oldest unreached mark whether it is ready, and where it
        is, the next: of the marks one look finds ready only the last is
        stamped (the others were reached at times nobody saw, so they go),
        and the first token's, which parts prompt from steps."""
        marks, now = self._marks, 0
        i = oldest = self._next
        while i < len(marks) and marks[i].array.is_ready():
            now = now or time.monotonic_ns()
            if i > oldest and i - 1 != self._first:
                marks[i - 1].time = None
            self._reach(marks[i], now)
            i += 1

    def _reach(self, mark: _Mark, now: int) -> None:
        mark.array, mark.time = None, now
        mark.lead = self._sent - mark.sent
        mark.gc, mark.switches = _gc_ns[0], _switches()
        self._next += 1

    def wait(self) -> None:
        """Everything is dispatched: wait for the marks not yet reached, in
        order, stamping each as it returns. The last is the last token."""
        self._dispatched = self._now
        self._look()
        while self._next < len(self._marks):
            mark = self._marks[self._next]
            with self.span("wait"):
                mark.array.block_until_ready()
            self._reach(mark, self._now)

    def _settle(self, _name: str, t0: int, t1: int) -> None:
        """The batch span's sink: its two clock readings are the batch."""
        if not self._whole:
            return

        def seconds(ns):
            return (ns - t0) / 1e9

        cpu_s = (time.thread_time_ns() - self._cpu0) / 1e9
        gc_end, switches_end = _gc_ns[0], _switches()
        kind = ("traced" if self._traced else
                "building" if _builds() != self._built else "steady")
        marks = [mark for mark in self._marks if mark.time is not None]
        calls = self._calls
        # the tail, last token to the batch's end, is the last interval
        seen = ([(m.phase, m.at, m.time, m.lead, m.gc, m.switches)
                 for m in marks] + [("tail", 1, t1, 0, gc_end, switches_end)])
        rows = _intervals(t0, calls[::2], calls[1::2],
                          [mark[:3] for mark in seen])
        gc_before, switches_before = self._gc0, self._switches0
        for row, (_, _, _, lead, gc_ns, switches) in zip(rows, seen):
            row["seconds"] /= 1e9
            row["between"] /= 1e9
            row["lead"], row["gc"] = lead, (gc_ns - gc_before) / 1e9
            row["switches"] = switches - switches_before
            gc_before, switches_before = gc_ns, switches
        stalls = find_stalls(rows)
        batch_s = seconds(t1)
        first = seconds(self._marks[self._first].time)
        steps = self._new_tokens - 1
        account = {
            "kind": kind, "rows": self._rows,
            "prompt_positions": self._positions, "steps": steps,
            "batch_s": batch_s, "prompt_s": first,
            "decode_s": batch_s - first, "tail_s": rows[-1]["seconds"],
            "dispatched_s": seconds(self._dispatched),
            "host_s": {name: ns / 1e9 for name, ns in self._host.items()},
            "cpu_s": cpu_s, "gc_s": (gc_end - self._gc0) / 1e9,
            "switches": switches_end - self._switches0,
            "marks": [[phase, at, round(seconds(t), 6), lead,
                       row["switches"]]
                      for (phase, at, t, lead, _, _), row in zip(seen, rows)],
            "longest": max(rows, key=lambda r: r["seconds"] / r["units"]),
            "stalls": stalls}
        if kind == "steady":
            M_SECONDS.inc(first, phase="prompt")
            M_SECONDS.inc(batch_s - first, phase="decode")
            M_POSITIONS.inc(self._rows * self._positions, phase="prompt")
            M_STEPS.inc(steps)
            M_HOST_CPU.inc(cpu_s)
            for stall in stalls:
                M_STALL_SECONDS.inc(stall["excess"], side=stall["side"])
        self._kept.append(account)
        lost = sum(stall["excess"] for stall in stalls)
        if kind != "building" and lost > WARN_SHARE * batch_s:
            logger.warning("generate: stalled batch: %s | %s",
                           stall_line(account), json.dumps(account))


def _place(row: dict) -> str:
    if row["phase"] == "tail":
        return "the tail"
    return (f"{row['phase']} {UNITS[row['phase']]} "
            f"{row['at'] - row['units']}..{row['at']}")


def stall_line(account: dict) -> str:
    """A batch's stalls in words: side, excess, place, the host's lead
    when the interval's end was seen, the collector's seconds in it and
    how often the thread was pre-empted."""
    return "; ".join(
        f"{s['side']} side +{s['excess']:.3f} s in {_place(s)} "
        f"({s['seconds']:.3f} s, {s['between']:.3f} s of it between calls, "
        f"host {s['lead']} ahead, gc {s['gc']:.3f} s, {s['switches']} "
        "involuntary switches)"
        for s in account["stalls"]) or "no stall"


def account_line(account: dict) -> str:
    """One line of a batch's account, as `tools/generate.py` prints it."""
    host = account["host_s"]
    steps = account["steps"]
    longest = account["longest"]
    return (
        f"account: {account['kind']} batch {account['batch_s']:.3f} s: "
        f"prompt {account['prompt_s']:.3f} s "
        f"({account['rows'] * account['prompt_positions'] / max(account['prompt_s'], 1e-9):.0f} tok/s), "
        f"decode {account['decode_s']:.3f} s"
        + (f" ({account['decode_s'] / steps * 1e3:.3f} ms a step)"
           if steps else "") + f", of it tail {account['tail_s']:.3f} s"
        + "; host " + " ".join(f"{name} {host[name]:.3f}"
                               for name in HOST_PHASES)
        + f", cpu {account['cpu_s']:.3f} s "
        f"({100 * account['cpu_s'] / max(account['batch_s'], 1e-9):.0f}%), "
        f"gc {account['gc_s']:.3f} s, {account['switches']} involuntary "
        f"switches; dispatched by {account['dispatched_s']:.3f} s, lead "
        f"{min(m[3] for m in account['marks'][:-1])}.."
        f"{max(m[3] for m in account['marks'][:-1])}; longest "
        f"{_place(longest)} {longest['seconds']:.3f} s; "
        f"{stall_line(account)}")
