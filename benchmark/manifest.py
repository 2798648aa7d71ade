"""`BENCHMARK.json` as the harness reads it, and the rules it is held to.

`problems` returns every breach of the limits a manifest is refused for
before a single run (names, units, counts, which metric moves which, files
found by name); the tests hold the committed manifest to it, and a later PR
that adds a cell can run it on its own."""
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(root):
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf8") as file:
        return json.load(file)


def workload(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                   f"{[cell['name'] for cell in manifest['workloads']]}")


def config_entry(manifest, name):
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return entry
    raise KeyError(f"BENCHMARK.json has no configuration {name!r}")


def traffic_path(root, manifest, mix):
    """The data file of traffic mix `mix`: `<path>/traffic/<mix>.<suffix>`
    under the first of `paths` that has one."""
    for base in manifest["paths"]:
        for suffix in TRAFFIC_SUFFIXES:
            path = os.path.join(root, base, "traffic", mix + suffix)
            if os.path.exists(path):
                return path
    return None


def reader_path(root, manifest, metric):
    """The reader of per-layer metric `metric`: `<path>/metrics/<metric>.py`."""
    for base in manifest["paths"]:
        path = os.path.join(root, base, "metrics", metric + ".py")
        if os.path.exists(path):
            return path
    return None


def metrics_of(manifest, group, cell_name):
    """The metrics of `group` (`end_to_end` or `per_layer`) that cell
    `cell_name` reports: those without a `workloads` key, and those that
    list it."""
    return [metric for metric in manifest[group]
            if "workloads" not in metric or cell_name in metric["workloads"]]


def problems(manifest, root):
    found = []

    def check(condition, message):
        if not condition:
            found.append(message)

    check(set(manifest) == TOP_KEYS, f"keys are {sorted(manifest)}")
    check(1 <= len(manifest["paths"]) <= 16, "1 to 16 paths")
    check(isinstance(manifest["run_seconds"], int)
          and 1 <= manifest["run_seconds"] <= 51, "run_seconds 1 to 51")
    command = manifest["command"]
    check(1 <= len(command) <= 32, "command of 1 to 32 words")
    for word in command:
        check(not word.startswith("/") and ".." not in word.split("/"),
              f"command word {word!r} leads out of the repo")
        if "/" in word:
            check(any(word.startswith(base + "/")
                      for base in manifest["paths"]),
                  f"command names {word!r}, outside paths")

    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in manifest[group]:
            check(NAME.match(entry["name"]), f"bad name {entry['name']!r}")
            check(entry["name"] not in seen, f"{entry['name']} twice")
            seen.add(entry["name"])
        if group in ("end_to_end", "per_layer"):
            check(not (seen & names), "a metric name is used twice")
            names |= seen

    config_names = {entry["name"] for entry in manifest["configs"]}
    files = set()
    for entry in manifest["configs"]:
        check(set(entry) == {"name", "source", "file", "reduced", "why"},
              f"configuration {entry['name']} has keys {sorted(entry)}")
        check(any(entry["file"].startswith(base + "/")
                  for base in manifest["paths"])
              and os.path.exists(os.path.join(root, entry["file"])),
              f"{entry['file']} is not a file under paths")
        check(entry["file"] not in files, f"{entry['file']} used twice")
        files.add(entry["file"])
        check(len(entry["reduced"]) <= 16
              and all(NAME.match(key) for key in entry["reduced"]),
              f"reduced of {entry['name']}")
        check(1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"])
              <= 200, f"why or source of {entry['name']}")

    cells = manifest["workloads"]
    check(1 <= len(cells) <= 24, "1 to 24 workloads")
    pairs = set()
    for cell in cells:
        check(set(cell) == {"name", "config", "traffic", "chips", "why"},
              f"workload {cell['name']} has keys {sorted(cell)}")
        check(cell["config"] in config_names,
              f"{cell['name']}: unknown configuration {cell['config']}")
        check(NAME.match(cell["traffic"]), f"bad traffic {cell['traffic']!r}")
        check(cell["chips"] in (1, 4), f"{cell['name']}: chips")
        check(1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
              and "\t" not in cell["why"], f"{cell['name']}: why")
        check((cell["config"], cell["traffic"]) not in pairs,
              f"{cell['name']}: configuration and traffic appear twice")
        pairs.add((cell["config"], cell["traffic"]))
        check(traffic_path(root, manifest, cell["traffic"]) is not None,
              f"{cell['name']}: no traffic file named {cell['traffic']}")
    check({cell["config"] for cell in cells} == config_names,
          "a configuration is used by no cell")
    four = sum(cell["chips"] == 4 for cell in cells)
    check(four <= max(1, len(cells) // 4), f"{four} four-chip cells")

    cell_names = {cell["name"] for cell in cells}
    check(1 <= len(manifest["end_to_end"]) <= 16, "1 to 16 end_to_end")
    check(1 <= len(manifest["per_layer"]) <= 128, "1 to 128 per_layer")
    for metric in manifest["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        check(set(metric) <= allowed and allowed - {"workloads"}
              <= set(metric), f"{metric['name']} has keys {sorted(metric)}")
        check(metric["source"] in ("host_clock", "device_trace"),
              f"{metric['name']}: source {metric['source']}")
        check(0.01 <= metric["bound"] <= 0.1, f"{metric['name']}: bound")
    check(any(m["name"] == "setup_s" and "workloads" not in m
              for m in manifest["end_to_end"]), "setup_s in every cell")
    for metric in manifest["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        check(set(metric) <= allowed and allowed - {"workloads"}
              <= set(metric), f"{metric['name']} has keys {sorted(metric)}")
        check(metric["source"] in SOURCES, f"{metric['name']}: source")
        check(1 <= len(metric["layer"]) <= 200, f"{metric['name']}: layer")
        check(reader_path(root, manifest, metric["name"]) is not None,
              f"{metric['name']}: no reader named after it")
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        check(UNIT.match(metric["unit"]), f"{metric['name']}: unit")
        check(metric["better"] in ("lower", "higher"),
              f"{metric['name']}: better")
        for cell in metric.get("workloads", ()):
            check(cell in cell_names, f"{metric['name']}: no cell {cell}")

    for cell in cells:
        end = {m["name"] for m in metrics_of(manifest, "end_to_end",
                                             cell["name"])}
        layer = metrics_of(manifest, "per_layer", cell["name"])
        check("setup_s" in end and len(end) >= 2,
              f"{cell['name']} reports {sorted(end)}")
        check(layer, f"{cell['name']} reports no per-layer metric")
        for metric in layer:
            check(metric["moves"] in end,
                  f"{metric['name']} moves {metric['moves']}, which "
                  f"{cell['name']} does not report")
    return found
