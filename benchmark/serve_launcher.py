"""`tools/serve.py`, started as its users start it, with a side door for
the two things only the process that holds the chip can give: the device's
memory counters and a profiler trace of a few seconds.

    python benchmark/serve_launcher.py <arguments of tools/serve.py>

The server's code runs unchanged as `__main__`. A thread reads commands from
standard input, one per line, and answers on standard output with a line
`bench: {...}`:

    stats                       devices and their peak bytes in use
    trace <directory> <seconds> one profiler session of that length, the
                                whole of it under the reduction's window
                                annotation

The thread sleeps in a blocking read between commands, so an untraced run
differs from the plain CLI by nothing that runs during the window."""
import json
import os
import runpy
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _answer(**facts):
    print("bench: " + json.dumps(facts), flush=True)


def _commands():
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            import jax
            if words[0] == "stats":
                from benchmark import device
                devices = jax.devices()
                _answer(stats=dict(
                    device.stamp(devices),
                    memory_peak_bytes=device.memory_peak_bytes(devices)))
            elif words[0] == "trace":
                from benchmark import xplane
                from benchmark.runners import common
                common.start_trace(words[1])
                try:
                    with jax.profiler.TraceAnnotation(xplane.WINDOW):
                        time.sleep(float(words[2]))
                finally:
                    jax.profiler.stop_trace()
                _answer(trace=words[1])
        except Exception as failure:    # noqa: BLE001 - the parent decides
            _answer(error=repr(failure))


def main():
    sys.path.insert(0, REPO)
    threading.Thread(target=_commands, daemon=True).start()
    server = os.path.join(REPO, "tools", "serve.py")
    sys.argv = [server] + sys.argv[1:]
    runpy.run_path(server, run_name="__main__")


if __name__ == "__main__":
    main()
