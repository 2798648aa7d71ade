"""How long an HTTP thread blocks reading one streamed token back from the
device: the mean `serve`/`readback` span of the window, from `/debug/spans`.
Nothing where the ring dropped spans: the mean would be of the window's
end.

Read only from a run on a chip (`peaks` in `observed`): in the CPU rehearsal
the same spans time XLA's CPU client, which is no number of this cell."""


def read(observed):
    if observed.get("spans_dropped") or "peaks" not in observed:
        return None
    spans = [span for span in observed.get("spans") or ()
             if span["cat"] == "serve" and span["name"] == "readback"]
    if not spans:
        return None
    return sum(span["t1"] - span["t0"] for span in spans) / len(spans) / 1e6
