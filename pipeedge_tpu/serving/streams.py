"""The server's one writer of streamed answers (docs/SERVING.md, "The
threads of a server").

The decode executor hands a tick's tokens over in ONE call
(`ContinuousBatcher(on_tokens=writer.hand_over)`): a list of `(stream, step,
tokens)`, one entry for every request that streams. `hand_over` appends the
list to a queue and wakes this module's one thread, which formats each
entry's line and writes it to that request's socket. So a step of 48 rows
costs the executor's worker one append and one wake-up, not a queue and a
thread a row, and the 48 handler threads sleep until their requests end.

A stream's socket is written by the writer alone from the `Stream`'s
making to its `closed` event: the handler thread sends the response headers
before it makes one and touches the socket again only after `closed`. The bytes
are what a handler thread used to write itself: one chunk of
`Transfer-Encoding: chunked` a line, `{"step", "tokens"}` a decode step in
order, then the final line, then the terminating chunk.

**One stalled client stalls nobody else.** Every send is non-blocking
(`MSG_DONTWAIT`; the socket's own mode is left alone, the connection is
`http.server`'s again afterwards). What the kernel does not take stays in
the stream's own buffer and is offered again after every hand-over, and
every `RETRY_SECONDS` while any stream holds bytes. A stream that holds more
than `BUFFER_BYTES`, or whose socket has taken nothing for `STALL_SECONDS`,
is dropped as a disconnected one is: its `cancel` flag is set, which ends
the request at the executor's next pick and frees its slot, and nothing more
is written to it.
"""
from __future__ import annotations

import json
import socket
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

from .. import telemetry
from ..telemetry import metrics as prom
from ..utils.threads import make_condition

BUFFER_BYTES = 1 << 18      # unsent bytes one stream may hold (8,000 lines)
STALL_SECONDS = 30.0        # how long its socket may take nothing
RETRY_SECONDS = 0.02        # the writer's nap while some stream holds bytes

M_HANDOVERS = prom.REGISTRY.counter(
    "pipeedge_stream_handovers_total",
    "hand-overs of streamed tokens from the decode executor to the "
    "server's writer thread (one a tick that brought tokens)")
M_STREAM_ROWS = prom.REGISTRY.counter(
    "pipeedge_stream_rows_total",
    "streamed lines those hand-overs carried (one a request a decode step): "
    "over pipeedge_stream_handovers_total the rows a hand-over, 1.0 where "
    "every token is handed over alone")
M_HANDOVERS.declare()
M_STREAM_ROWS.declare()


def chunk(obj) -> bytes:
    """One ndjson line as one chunk of a chunked response."""
    data = json.dumps(obj).encode() + b"\n"
    return f"{len(data):x}\r\n".encode() + data + b"\r\n"


LAST_CHUNK = b"0\r\n\r\n"


class Stream:
    """One streamed response over `sock`, whose headers are out, to its
    last chunk. The writer's thread alone touches it; the handler thread
    makes it and reads `closed`."""
    __slots__ = ("sock", "rid", "cancel", "t0", "steps", "first_ms",
                 "unsent", "took_at", "ending", "closed")

    def __init__(self, sock, rid, cancel, t0: float):
        self.sock = sock
        self.rid = rid
        self.cancel = cancel        # set: the client is gone, write no more
        self.t0 = t0                # the request's receipt (first_token_ms)
        self.steps = 0
        self.first_ms: Optional[float] = None
        self.unsent = bytearray()   # what the kernel has not taken yet
        self.took_at = 0.0          # when its socket last took bytes
        self.ending = False         # its final line has been offered
        self.closed = threading.Event()


class StreamWriter:
    """The one thread that writes every stream's lines.

    >>> writer = StreamWriter().start()
    >>> executor = ContinuousBatcher(pipe, on_tokens=writer.hand_over)
    >>> stream = Stream(sock, rid, cancel, t0)         # headers are out
    >>> executor.submit(rid, ids, n, cancel=cancel, stream=stream)
    >>> writer.finish(stream, ids=executor.wait(rid).tolist())
    >>> stream.closed.wait()
    """

    def __init__(self):
        self._cond = make_condition("serve.streams")
        self._queue: deque = deque()
        self._held: dict = {}       # streams with unsent bytes (ordered)
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "StreamWriter":
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="stream-writer")
            self._thread.start()
        return self

    def hand_over(self, rows: list) -> None:
        """The executor's `on_tokens`: a tick's `(stream, step, tokens)`,
        taken in one append. Never blocks on a socket."""
        self._put((None, rows))

    def finish(self, stream: Stream, ids=None, error=None) -> None:
        """The request has ended: after every line handed over before this
        call, write its final line (`ids`: the result's rows; `error`: what
        failed instead) and the last chunk, then set `stream.closed`."""
        self._put((stream, {"error": error} if ids is None
                   else {"ids": ids}))

    def _put(self, item) -> None:
        with self._cond:
            if self._stop and item[0] is not None:
                item[0].closed.set()    # no writer left to write it
                return
            self._queue.append(item)
            self._cond.notify()

    def stop(self) -> None:
        """Stop the thread; every stream still open is closed unwritten."""
        with self._cond:
            self._stop = True
            self._cond.notify()
        if self._thread is not None:
            self._thread.join()

    # -- the writer's thread ------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._cond:
                if not self._queue and not self._stop:
                    self._cond.wait(RETRY_SECONDS if self._held else None)
                items = list(self._queue)
                self._queue.clear()
                stopping = self._stop
            for stream, payload in items:
                if stream is None:
                    self._flush(payload)
                else:
                    self._end(stream, payload)
            self._offer_held()
            if stopping:
                for stream in list(self._held):
                    self._drop(stream)
                return

    def _flush(self, rows: list) -> None:
        """`serve/flush`: one hand-over's lines, each to its own socket."""
        M_HANDOVERS.inc()
        M_STREAM_ROWS.inc(len(rows))
        with telemetry.span("serve", "flush"):
            for stream, step, token in rows:
                try:
                    self._line(stream, step, token)
                except Exception:   # noqa: BLE001 - a failed read-back is
                    self._drop(stream)      # that request's, not the writer's

    def _line(self, stream: Stream, step: int, token) -> None:
        # the blocking device read-back of a request that steps alone
        # happens HERE; the rows that step together arrive as host integers
        with telemetry.span("serve", "readback", rid=stream.rid):
            tokens = np.asarray(token).tolist()
        if not stream.cancel.is_set():
            with telemetry.span("serve", "write", rid=stream.rid):
                self._send(stream, chunk({"step": step, "tokens": tokens}))
        if stream.first_ms is None:
            stream.first_ms = round((time.monotonic() - stream.t0) * 1e3, 3)
        stream.steps += 1

    def _end(self, stream: Stream, final: dict) -> None:
        if "ids" in final:
            final.update(first_token_ms=stream.first_ms, steps=stream.steps)
        final["rid"] = stream.rid
        for data in (chunk(final), LAST_CHUNK):
            if not stream.cancel.is_set():      # the first send may drop it
                self._send(stream, data)
        stream.ending = True
        self._close_if_sent(stream)

    def _close_if_sent(self, stream: Stream) -> None:
        """An ended stream closes when the kernel has all it is owed."""
        if stream.ending and not stream.unsent:
            stream.closed.set()

    def _send(self, stream: Stream, data: bytes) -> None:
        """Offer `data` to the stream's socket behind what it still holds;
        what the kernel does not take now waits in `unsent`."""
        if stream.unsent:
            stream.unsent += data
            if len(stream.unsent) > BUFFER_BYTES:
                self._drop(stream)
            return
        taken = self._offer(stream, data)
        if taken is not None and taken < len(data):
            stream.unsent += data[taken:]
            stream.took_at = time.monotonic()
            self._held[stream] = None

    def _offer(self, stream: Stream, data) -> Optional[int]:
        """A non-blocking send. -> bytes taken; None: the stream is gone."""
        try:
            return stream.sock.send(data, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            return 0
        except OSError:
            # client went away: cancel the generation (it completes early
            # at its next pick, releasing the executor slot)
            self._drop(stream)
            return None

    def _offer_held(self) -> None:
        """Offer every waiting stream's bytes again."""
        now = time.monotonic()
        for stream in list(self._held):
            taken = self._offer(stream, stream.unsent)
            if taken is None:
                continue
            if taken:
                del stream.unsent[:taken]
                stream.took_at = now
            if not stream.unsent:
                del self._held[stream]
                self._close_if_sent(stream)
            elif now - stream.took_at > STALL_SECONDS:
                self._drop(stream)

    def _drop(self, stream: Stream) -> None:
        """A client that is gone, or as good as: nothing more is written."""
        stream.cancel.set()
        stream.unsent.clear()
        self._held.pop(stream, None)
        self._close_if_sent(stream)
