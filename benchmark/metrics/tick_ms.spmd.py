"""Host-clock seconds of the window over the SPMD program's ticks
(microbatches + stages - 1 a round), results read back each round."""


def read(observed):
    if observed["traffic"].get("driver") != "spmd" or not observed.get("ticks"):
        return None
    return observed["window_s"] / observed["ticks"] * 1e3
