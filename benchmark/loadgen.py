"""The open-loop load generator: a schedule from a traffic file and a seed,
an HTTP client that streams each answer, and the percentile arithmetic.

Independent users make an open loop: a request is sent when it is due,
whether or not earlier ones have finished, and its latency counts from
when it was due, so a stall shows in the requests behind it. How late the
generator itself ran is reported beside the latencies.

Every seed gives the same multiset of prompt lengths, answer lengths and
gaps between arrivals, in another order, and every stretch of about ten
requests holds the whole range of each (`_deal`): the seed must not change
the amount of work or bunch it, only which request meets which. The arrival arithmetic and
the nearest-rank percentile follow `tools/loadgen.py` (which a later PR may
change; this copy is the yardstick's)."""
import dataclasses
import http.client
import json
import math
import threading
import time

import numpy as np


def percentile(values, q):
    """Nearest-rank percentile; None of nothing."""
    if not values:
        return None
    ordered = sorted(values)
    index = min(len(ordered) - 1,
                max(0, int(round(q / 100.0 * (len(ordered) - 1)))))
    return ordered[index]


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # from the start of the window
    ids: list               # the prompt's tokens
    new_tokens: int
    # filled in by the client
    sent_s: float = None
    token_s: list = dataclasses.field(default_factory=list)
    done_s: float = None
    status: int = None
    error: str = None
    answer: list = None     # prompt and generated tokens, as the server
                            # returned them on the final line

    @property
    def ok(self):
        return (self.status == 200 and self.error is None
                and self.answer is not None
                and len(self.answer) == len(self.ids) + self.new_tokens)


def _apportion(weights, n):
    """`n` items over the choices in proportion to `weights`, by largest
    remainder, so that the counts depend on nothing but `n`."""
    total = float(sum(weights))
    exact = [n * w / total for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    by_remainder = sorted(range(len(weights)),
                          key=lambda i: (exact[i] - counts[i], -i),
                          reverse=True)
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _lengths(spec, n):
    """The fixed multiset of `n` lengths a traffic file describes: either
    `{"choices": [...], "weights": [...]}` or `{"log_uniform": [lo, hi]}`
    (the `n` mid-quantiles of that distribution, rounded)."""
    if "choices" in spec:
        weights = spec.get("weights") or [1] * len(spec["choices"])
        counts = _apportion(weights, n)
        return [choice for choice, count in zip(spec["choices"], counts)
                for _ in range(count)]
    low, high = spec["log_uniform"]
    return [int(round(math.exp(math.log(low) + (i + 0.5) / n
                               * (math.log(high) - math.log(low)))))
            for i in range(n)]


def _gaps(arrivals, rate, n):
    """The fixed multiset of `n` gaps: the mid-quantiles of an exponential
    of mean 1/rate (`poisson`), or all 1/rate (`uniform`)."""
    if arrivals == "uniform":
        return [1.0 / rate] * n
    if arrivals != "poisson":
        raise ValueError(f"unknown arrivals {arrivals!r}")
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


BLOCK = 10      # requests (or bursts) a block; see `_deal`


def _deal(values, rng, block=BLOCK):
    """`values` (a fixed multiset, sorted or not) in an order drawn from
    `rng` that keeps every stretch of the schedule alike: the sorted values
    are dealt round-robin into blocks of about `block`, so that each block
    holds the whole range, then each block is shuffled and so is the order
    of the blocks. A plain shuffle can put the long gaps or the long answers
    together, and at a rate near capacity that alone moved the served rate
    by 5% between seeds (my chip runs, PR 23)."""
    ordered = sorted(values)
    blocks = max(1, len(ordered) // block)
    dealt = [ordered[b::blocks] for b in range(blocks)]
    out = []
    for b in rng.permutation(blocks):
        out.extend(dealt[b][i] for i in rng.permutation(len(dealt[b])))
    return out


def schedule(traffic, vocabulary, seconds, seed):
    """The window's requests, in due order. `traffic` gives `rate_per_s`,
    `arrivals`, `prompt_len`, `new_tokens` and optionally `burst` (that many
    requests share each due time) and `shared_prefix` (`groups` and
    `tokens`: requests of one group start with the same tokens)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    burst = int(traffic.get("burst", 1))
    groups = -(-n // burst)
    gaps = _deal(_gaps(traffic["arrivals"], traffic["rate_per_s"] / burst,
                       groups), rng)
    dues = np.cumsum(gaps) - gaps[0]        # the first is due at once
    prompt_lens = _deal(_lengths(traffic["prompt_len"], n), rng)
    answers = _deal(_lengths(traffic["new_tokens"], n), rng)
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [rng.integers(0, vocabulary, size=shared["tokens"])
                    for _ in range(shared["groups"])]
    requests = []
    for i in range(n):
        ids = rng.integers(0, vocabulary, size=prompt_lens[i])
        if prefixes:
            prefix = prefixes[i % len(prefixes)][:prompt_lens[i] - 1]
            ids[:len(prefix)] = prefix
        requests.append(Request(index=i, due_s=float(dues[i // burst]),
                                ids=[int(t) for t in ids],
                                new_tokens=answers[i]))
    return requests


def _stream(host, port, request, origin, timeout):
    """Send one request and read its answer line by line, stamping each
    streamed token as it arrives."""
    body = json.dumps({"ids": [request.ids], "new_tokens": request.new_tokens,
                       "stream": True})
    connection = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        request.sent_s = time.monotonic() - origin
        connection.request("POST", "/generate", body=body,
                           headers={"Content-Type": "application/json"})
        response = connection.getresponse()
        request.status = response.status
        if response.status != 200:
            request.error = response.read(2000).decode("utf8", "replace")
            return
        while True:
            line = response.readline()
            if not line:
                break
            now = time.monotonic() - origin
            if not line.strip():
                continue
            row = json.loads(line)
            if "tokens" in row:
                request.token_s.append(now)
            elif "error" in row:
                request.error = str(row["error"])
            elif "ids" in row:
                request.answer = row["ids"][0]
                request.done_s = now
    except Exception as failure:    # noqa: BLE001 - counted, not raised
        request.error = repr(failure)
    finally:
        connection.close()


def drive(host, port, requests, timeout=120.0):
    """Send every request when it is due (one thread each, started at its
    due time) and wait for all answers. Returns the seconds from the first
    due time to the last answer."""
    origin = time.monotonic()
    threads = []
    for request in requests:
        wait = request.due_s - (time.monotonic() - origin)
        if wait > 0:
            time.sleep(wait)
        thread = threading.Thread(
            target=_stream, args=(host, port, request, origin, timeout),
            daemon=True)
        thread.start()
        threads.append(thread)
    for thread in threads:
        thread.join(timeout)
    return time.monotonic() - origin


def summarize(requests):
    """Latencies of a driven schedule, in milliseconds. A request that
    failed or was shed has no time to first token: it counts as missing
    every limit, which a percentile shows as infinity once such requests
    are more than the share above it."""
    ttft, gaps, late = [], [], []
    for request in requests:
        if request.sent_s is not None:
            late.append((request.sent_s - request.due_s) * 1e3)
        if not request.ok or not request.token_s:
            ttft.append(math.inf)
            continue
        ttft.append((request.token_s[0] - request.due_s) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in
                    zip(request.token_s, request.token_s[1:]))
    good = [r for r in requests if r.ok]
    return {
        "sent": sum(r.sent_s is not None for r in requests),
        "succeeded": len(good),
        "failed": len(requests) - len(good),
        "tokens": sum(r.new_tokens for r in good),
        "streamed_tokens": sum(len(r.token_s) for r in requests),
        "failures": [(r.status, r.error, r.new_tokens,
                      None if r.answer is None else len(r.answer) - len(r.ids))
                     for r in requests if not r.ok][:5],
        "ttft_ms": ttft, "itl_ms": gaps, "late_ms": late,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p95_ms": percentile(ttft, 95),
        "itl_p50_ms": percentile(gaps, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "gen_late_p95_ms": percentile(late, 95),
    }
