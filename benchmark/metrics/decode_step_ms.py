"""Host-clock seconds of the window over the decode steps taken in it, each
batch fenced by reading its tokens back. The window also holds one prefill
per batch (one per `new_tokens - 1` steps), which this charges to the
steps."""


def read(observed):
    steps = observed.get("decode_steps")
    return observed["window_s"] / steps * 1e3 if steps else None
