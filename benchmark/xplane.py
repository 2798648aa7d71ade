"""From a profiler trace (`.xplane.pb`) to device busy time, per-operation
time and the idle gaps named by what the host was doing.

`read` turns the file into plain lists of (name, start_ns, end_ns); `reduce`
is arithmetic on those lists and is what the tests exercise. A trace holds
one plane per device ("/device:TPU:0", ...) whose "XLA Ops" line carries one
event per executed operation, and host planes whose lines are threads; the
benchmark marks the measured part of the trace with a host annotation named
`WINDOW` so that busy and idle are shares of a window it chose, on the
profiler's own clock."""
import glob
import os
import re
import shutil

import numpy as np

WINDOW = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LINES = "python"
TOP = 10
# gaps named one by one; the rest are summed under SHORT_GAPS
NAMED_GAPS = 2000
SHORT_GAPS = "(gaps beyond the longest 2000)"
NO_HOST_EVENT = "(between host events)"


def find_trace(trace_dir):
    """The newest .xplane.pb under a directory `jax.profiler` wrote to."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def short_name(text):
    """`%fusion f32[1024]` from the HLO instruction text the profiler
    gives as an operation's name; other names as they are."""
    head, found, rest = text.partition(" = ")
    if not found:
        return text[:120]
    # `%fusion.84` and `%fusion.85` are one kind of operation in two
    # layers: the number goes, the output's shape tells kinds apart
    head = re.sub(r"\.\d+$", "", head)
    shape = rest.split("{")[0].split(" ")[0].lstrip("(")
    return f"{head} {shape}"[:120]


def _module_of(modules, starts, start):
    """Name of the module event that contains time `start`, without the
    fingerprint in brackets; "" if none does."""
    import bisect
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and modules[i][2] >= start:
        return modules[i][0].split("(")[0]
    return ""


def read(path, device_plane=DEVICE_PLANE, ops_line=OPS_LINE,
         modules_line=MODULES_LINE, host_lines=HOST_LINES):
    """(device_ops, host_events): `device_ops` maps a device plane's name to
    its operations, each named `<module>/<instruction> <shape>`;
    `host_events` lists the events of the host's Python threads (where
    TraceAnnotations and jitted calls show); both as (name, start_ns,
    end_ns)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    device_ops, host_events = {}, []
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (event.name, int(event.start_ns),
                 int(event.start_ns + event.duration_ns))
                for event in line.events)
        if plane.name.startswith(device_plane):
            modules = sorted(lines.get(modules_line, ()),
                             key=lambda event: event[1])
            starts = [event[1] for event in modules]
            device_ops[plane.name] = [
                (f"{_module_of(modules, starts, start)}/{short_name(name)}",
                 start, end)
                for name, start, end in lines.get(ops_line, ())]
        elif not plane.name.startswith("/device:"):
            for name, rows in lines.items():
                if name.startswith(host_lines):
                    host_events.extend(rows)
    return device_ops, host_events


def union(intervals):
    """Merged, sorted [start, end) pairs covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(ops):
    """Seconds by operation name, each instant charged to the innermost
    operation running then (a `while` is not charged for its body)."""
    totals = {}
    stack = []      # (name, end) of the operations open at the sweep point
    last = None

    def charge(until):
        if stack and until > last:
            name = stack[-1][0]
            totals[name] = totals.get(name, 0) + (until - last)

    for name, start, end in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][1] <= start:
            closing = stack[-1][1]
            charge(closing)
            last = max(last, closing)
            stack.pop()
        if stack:
            charge(start)
        last = start
        stack.append((name, end))
    while stack:
        closing = stack[-1][1]
        charge(closing)
        last = max(last, closing)
        stack.pop()
    return {name: ns / 1e9 for name, ns in totals.items()}


def _name_gaps(gaps, host_events):
    """Seconds of idle by the innermost host event that covers at least
    half of each gap."""
    named = {}
    gaps = sorted(gaps, key=lambda gap: gap[0] - gap[1])    # longest first
    rest = sum(end - start for start, end in gaps[NAMED_GAPS:])
    if rest:
        named[SHORT_GAPS] = rest
    if host_events:
        names = [event[0] for event in host_events]
        starts = np.array([event[1] for event in host_events], np.int64)
        ends = np.array([event[2] for event in host_events], np.int64)
        lengths = ends - starts
    for start, end in gaps[:NAMED_GAPS]:
        name = NO_HOST_EVENT
        if host_events:
            overlap = np.minimum(ends, end) - np.maximum(starts, start)
            covering = np.flatnonzero(2 * overlap >= end - start)
            if len(covering):
                name = names[covering[np.argmin(lengths[covering])]]
        named[name] = named.get(name, 0) + (end - start)
    return {name: ns / 1e9 for name, ns in named.items()}


def reduce(device_ops, host_events, window=None):
    """Busy and idle seconds of each device over the window, the operations
    that took most time and the idle gaps by host event.

    The window is `window` (start_ns, end_ns) if given, else the host event
    named `WINDOW`, else from the first operation's start to the last one's
    end. Operations are clipped to it. Returns None where no operation ran
    in the window."""
    if window is None:
        marks = [(s, e) for name, s, e in host_events if name == WINDOW]
        if marks:
            window = max(marks, key=lambda mark: mark[1] - mark[0])
    every = [op for ops in device_ops.values() for op in ops]
    if window is None and every:
        window = (min(op[1] for op in every), max(op[2] for op in every))
    if window is None or window[1] <= window[0]:
        return None
    w0, w1 = window
    per_chip, gaps, by_op = {}, [], {}
    for chip in sorted(device_ops):
        clipped = [(name, max(start, w0), min(end, w1))
                   for name, start, end in device_ops[chip]
                   if end > w0 and start < w1]
        for name, seconds in self_times(clipped).items():
            by_op[name] = by_op.get(name, 0.0) + seconds
        merged = union((start, end) for _, start, end in clipped)
        per_chip[chip] = sum(end - start for start, end in merged) / 1e9
        edges = [w0] + [t for pair in merged for t in pair] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if not by_op:
        return None
    chips = len(per_chip)
    others = [event for event in host_events if event[0] != WINDOW]
    by_host = _name_gaps(gaps, others)

    def top(table):
        rows = sorted(table.items(), key=lambda row: -row[1])[:TOP]
        return [[name, seconds / chips] for name, seconds in rows]

    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(per_chip.values()) / chips,
            "busy_s_per_chip": per_chip,
            "device_ops": top(by_op),       # seconds a chip, self time
            "idle_gaps": top(by_host)}      # seconds a chip


def idle_share(trace):
    """Percent of the traced window in which no operation ran, mean over
    the chips; None without a trace."""
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def reduce_dir(trace_dir, keep=False):
    """`reduce` of the newest trace under `trace_dir`, or None. The trace
    itself (tens of megabytes) is deleted unless `keep`."""
    path = find_trace(trace_dir)
    reduced = None if path is None else reduce(*read(path))
    if not keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced
