"""The executor worker's headroom: seconds gained by `exec/wait0` (blocked
for work, or for the lock it shares with the HTTP threads) over the seconds
gained by every phase of the worker (`stage/exec0` and the whole `exec`
category), between the scrapes before and after the window, in percent.
Near 0 the worker sets the server's pace.

Read only from a run on a chip (`peaks` in `observed`): in the CPU rehearsal
the same spans time XLA's CPU client, which is no number of this cell."""
from benchmark import prom

FAMILY = "pipeedge_span_seconds_total"


def read(observed):
    if "metrics_after" not in observed or "peaks" not in observed:
        return None

    def total(text, wanted):
        return sum(value for labels, value in prom.samples(text, FAMILY)
                   if wanted(labels.get("cat"), labels.get("name")))

    def gained(wanted):
        return total(observed["metrics_after"], wanted) \
            - total(observed["metrics_before"], wanted)

    waited = gained(lambda cat, name: (cat, name) == ("exec", "wait0"))
    worked = gained(lambda cat, name: cat == "exec"
                    or (cat, name) == ("stage", "exec0"))
    return 100.0 * waited / worked if worked > 0 else None
