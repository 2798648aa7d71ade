"""What the run's device is and what it can do at best.

`peaks_for` reads the one table of published peaks (`peaks.json`), keyed by
the `device_kind` JAX reports; a kind that is not in the table is an error,
never a default. `stamp` and `memory_peak_bytes` are called only in the
process that holds the chip."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    """The run found another platform, or fewer chips, than its cell needs."""


def peaks_for(kind):
    with open(os.path.join(HERE, "peaks.json"), encoding="utf8") as table:
        kinds = json.load(table)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"benchmark/peaks.json knows {sorted(kinds)}")
    return kinds[kind]


def stamp(devices):
    """Platform, kind and count of `devices` as JAX reports them."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require(stamp_, chips, platforms=("tpu",)):
    """Raise unless the process runs on `chips` devices of an accelerator.
    `platforms` is widened only by the CPU rehearsal tests, never by a
    command-line option."""
    if stamp_["platform"] not in platforms or stamp_["count"] < chips:
        raise NoAccelerator(
            f"cell needs {chips} chip(s) of {platforms}; JAX reports {stamp_}")


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest device (None where the backend
    keeps no count, as the CPU's does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
