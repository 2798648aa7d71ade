"""The weight loader alone on one configuration's file: what
`setup_weights_s` is made of, without a cell around it.

Writes the seeded weights of `--config` (`benchmark/configs/<name>.json`)
as the benchmark does (`benchmark.weights.write`: `np.savez`, float16, the
published key scheme), then loads them once as every builder does
(`models/registry.py::module_shard_factory`, the whole model as one stage)
and says where the seconds went:

- `file`: writing the file (the benchmark's, not the program's);
- `map`: `_TimedReads` opened and every member handed out once: the zip's
  directory, a local header and an `.npy` header a member, no array byte
  touched (a commit whose `_TimedReads` reads, PR 52 and before, reads
  here: that is `np.load`'s rate);
- `old_read` (`--old-read`): every member through `np.load`, what the
  loader did until PR 53;
- `load`: the whole load, and of the seven sparse families' `on_device`
  each leaf's `copy` (made on the host from the file's pages: the one host
  copy), `transfer` (the call that starts its transfer and cast) and
  `wait` (the trailing fence, a leaf behind), read off two seams the tool
  times from outside (`decoder._host`, `jax.block_until_ready`): the loop's
  order is host n, wait n - 1, start n, so what lies between a wait's end
  and the next host's start is a transfer's call. A commit without the
  seam gives the whole and the counters alone;
- the process's own account (`pipeedge_startup_seconds_total`,
  `..._bytes_total`, `pipeedge_weights_members_total`) and the device's
  peak and resident bytes after the load.

One JSON line each; `--leaves N` adds the N slowest leaves. No cell runs
this: `setup_weights_s` of the cells is the driver's number, this is where
to look when it moves (ROADMAP S9, S13).

Usage: python tools/bench_loader.py [--config keye-vl-2.0-30b-a3b]
    [--seed 1] [--old-read] [--leaves 8] [--tiny]
`--tiny` loads `pipeedge/test-tiny-keye` from a file of drawn values (a
rehearsal on the CPU: no time of it means anything).
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _rate(nbytes, seconds):
    return {"s": round(seconds, 4), "GB": round(nbytes / 1e9, 4),
            "GB/s": round(nbytes / 1e9 / seconds, 3) if seconds else None}


def _account():
    """{family: {label value: count}} of the loader's three counter
    families, as the process's registry stands."""
    from benchmark import prom as bench_prom
    from pipeedge_tpu.telemetry import metrics
    text = metrics.REGISTRY.render()
    return {family: {labels[label]: value for labels, value
                     in bench_prom.samples(text, family)}
            for family, label in (
                ("pipeedge_startup_seconds_total", "phase"),
                ("pipeedge_startup_bytes_total", "phase"),
                ("pipeedge_weights_members_total", "path"))}


def _tiny_file(model, path):
    """A state-dict npz of drawn float16 values with the keys and shapes
    `model`'s loader asks for."""
    import jax.numpy as jnp
    import numpy as np
    from pipeedge_tpu.models import ShardConfig, registry
    entry = registry.get_model_entry(model)
    rng, tensors = np.random.default_rng(0), {}

    def get(key, shape):
        tensors[key] = rng.normal(0, 0.02, shape).astype(np.float16)
        return tensors[key]
    entry.family._assemble(
        entry.config, ShardConfig(1, entry.layers, True, True), get,
        jnp.float32)
    np.savez(path, **tensors)


def _timed_load(model, path, dtype):
    """(seconds of the whole load, events): `module_shard_factory` with
    `decoder._host` and `jax.block_until_ready` timed from outside, each
    event (kind, start, end, bytes made)."""
    import jax
    import numpy as np
    from pipeedge_tpu.models import decoder, registry
    events = []
    host, wait = getattr(decoder, "_host", None), jax.block_until_ready

    def timed_host(leaf, rooms):
        start = time.perf_counter()
        out = host(leaf, rooms)
        made = out.nbytes if isinstance(out, np.ndarray) and out is not leaf \
            else 0
        events.append(("copy", start, time.perf_counter(), made))
        return out

    def timed_wait(tree):
        start = time.perf_counter()
        out = wait(tree)
        events.append(("wait", start, time.perf_counter(), 0))
        return out

    if host is not None:
        decoder._host, jax.block_until_ready = timed_host, timed_wait
    try:
        start = time.perf_counter()
        _, params, _ = registry.module_shard_factory(
            model, path, 1, registry.get_model_layers(model), dtype=dtype,
            unroll=False)
        wait(params)
        whole = time.perf_counter() - start
    finally:
        if host is not None:
            decoder._host, jax.block_until_ready = host, wait
    return whole, events, params


def _leaves(events):
    """[{bytes, copy_s, transfer_s, wait_s}] a leaf, in the loader's order,
    from the events of `_timed_load`: host 0, wait (for nothing), host 1,
    wait 0, ..., host n - 1, wait n - 2, wait n - 1."""
    copies = [event for event in events if event[0] == "copy"]
    waits = [event for event in events if event[0] == "wait"]
    if len(waits) != len(copies) + 1:
        return []       # not `on_device`'s loop: no breakdown
    starts = [start for _, start, _, _ in copies[1:]] + [waits[-1][1]]
    return [{"bytes": made, "copy_s": end - start,
             "transfer_s": then - before[2],
             "wait_s": after[2] - after[1]}
            for (_, start, end, made), before, after, then
            in zip(copies, waits, waits[1:], starts)]


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", default="keye-vl-2.0-30b-a3b",
                   help="a file of benchmark/configs, without .json")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--work", default=os.path.join(
        REPO, "benchmark", ".work", "loader"))
    p.add_argument("--old-read", action="store_true",
                   help="also read every member with np.load")
    p.add_argument("--leaves", type=int, default=0,
                   help="print the N leaves that took longest")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights
    from pipeedge_tpu.models import registry
    device = jax.devices()[0]
    print("devices:", json.dumps({
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count()}))
    os.makedirs(args.work, exist_ok=True)
    start = time.perf_counter()
    if args.tiny:
        model, dtype = "pipeedge/test-tiny-keye", jnp.bfloat16
        path = os.path.join(args.work, "tiny.npz")
        _tiny_file(model, path)
    else:
        with open(os.path.join(REPO, "benchmark", "configs",
                               args.config + ".json")) as file:
            config = json.load(file)
        model = config["program_model"]
        dtype = jnp.bfloat16 if config["dtype"] == "bfloat16" \
            else jnp.float32
        path = weights.write(config, args.seed, os.path.join(
            args.work, registry.get_model_default_weights_file(model)))
    print(json.dumps({"phase": "file", "model": model,
                      **_rate(os.path.getsize(path),
                              time.perf_counter() - start)}))
    try:
        start = time.perf_counter()
        with registry._TimedReads(path) as members:
            nbytes = sum(members[key].nbytes for key in members)
            count = len(members)
        print(json.dumps({"phase": "map", "members": count,
                          **_rate(nbytes, time.perf_counter() - start)}))
        if args.old_read:
            start = time.perf_counter()
            with np.load(path) as file:
                nbytes = sum(file[key].nbytes for key in file.files)
            print(json.dumps({"phase": "old_read",
                              **_rate(nbytes, time.perf_counter() - start)}))
        before = _account()
        whole, events, params = _timed_load(model, path, dtype)
        placed = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
        leaves = _leaves(events)
        line = {"phase": "load", **_rate(placed, whole),
                "leaves": len(jax.tree_util.tree_leaves(params))}
        if leaves:
            copied = sum(leaf["bytes"] for leaf in leaves)
            line.update(
                copy=_rate(copied, sum(leaf["copy_s"] for leaf in leaves)),
                transfer=_rate(copied, sum(leaf["transfer_s"]
                                           for leaf in leaves)),
                wait=_rate(copied, sum(leaf["wait_s"] for leaf in leaves)))
        print(json.dumps(line))
        for leaf in sorted(leaves, key=lambda leaf: -sum(
                leaf[k] for k in ("copy_s", "transfer_s", "wait_s"))
                )[:args.leaves]:
            print(json.dumps({"phase": "leaf", **{
                k: round(v, 4) for k, v in leaf.items()}}))
        print(json.dumps({"phase": "account", **{
            family: {key: round(value - before[family].get(key, 0.0), 4)
                     for key, value in now.items()
                     if value != before[family].get(key, 0.0)}
            for family, now in _account().items()}}))
        stats = device.memory_stats() or {}
        print(json.dumps({"phase": "device", "resident_bytes":
                          stats.get("bytes_in_use"),
                          "peak_bytes": stats.get("peak_bytes_in_use")}))
    finally:
        os.remove(path)


if __name__ == "__main__":
    main()
