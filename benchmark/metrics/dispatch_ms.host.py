"""Host time to dispatch one microbatch into one stage: the mean of the
host driver's `stage`/`stage<i>` spans (`HostPipeline.enqueue`)."""


def read(observed):
    spans = [span for span in observed.get("spans") or ()
             if span["cat"] == "stage" and span["name"].startswith("stage")]
    if not spans:
        return None
    return sum(span["t1"] - span["t0"] for span in spans) / len(spans) / 1e6
