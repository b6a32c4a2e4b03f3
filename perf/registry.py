"""Everything the harness runs is found by name in these directories.

A later PR adds a configuration, a traffic mix, a cell, a metric, a reader,
a generator or a runner by adding a file; nothing here lists them.

    perf/configs/<name>.json     one model configuration, as it is run
    perf/traffic/<name>.json     one traffic mix: {"generator": ..., parameters}
    perf/workloads/<name>.json   one cell: configuration, traffic, chips, runner,
                                 job or engine parameters, the metrics it reports
    perf/metrics/<name>.json     one metric: unit, better, source, and for a
                                 per-layer metric its layer, `moves`, reader
                                 and, optionally, the cells it is read in
                                 beside those that list it (`workloads`)
    perf/generators/<name>.py    traffic generator:  generate(params, ...)
    perf/runners/<name>.py       runs a cell:        run(cell, args) -> Result
    perf/readers/<name>.py       reads one metric:   read(obs, **args) -> float | None
    perf/families/<name>.py      one model family: the configuration file ->
                                 the program's GPTConfig, which of its keys
                                 are widths, the plain reference, the counts
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class RegistryError(Exception):
    """A file is missing, malformed, or names something that does not exist."""


def _load(kind: str, name: str) -> Dict[str, Any]:
    if not NAME_RE.match(name):
        raise RegistryError(f"bad {kind} name {name!r}")
    path = os.path.join(ROOT, kind, name + ".json")
    if not os.path.isfile(path):
        have = ", ".join(names(kind)) or "none"
        raise RegistryError(f"no {kind} file {path} (have: {have})")
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise RegistryError(f"{path} is not a JSON object")
    data.setdefault("name", name)
    if data["name"] != name:
        raise RegistryError(f"{path} says name {data['name']!r}")
    return data


def names(kind: str) -> List[str]:
    """Names of the .json (data) or .py (code) entries of one directory."""
    directory = os.path.join(ROOT, kind)
    if not os.path.isdir(directory):
        return []
    out = []
    for entry in sorted(os.listdir(directory)):
        stem, ext = os.path.splitext(entry)
        if ext in (".json", ".py") and not stem.startswith("__"):
            out.append(stem)
    return out


def config(name: str) -> Dict[str, Any]:
    return _load("configs", name)


def traffic(name: str) -> Dict[str, Any]:
    data = _load("traffic", name)
    if "generator" not in data:
        raise RegistryError(f"traffic {name!r} names no generator")
    return data


def metric(name: str) -> Dict[str, Any]:
    data = _load("metrics", name)
    for key in ("unit", "better", "source"):
        if key not in data:
            raise RegistryError(f"metric {name!r} lacks {key!r}")
    if data["better"] not in ("lower", "higher"):
        raise RegistryError(f"metric {name!r}: better={data['better']!r}")
    if data["source"] not in SOURCES:
        raise RegistryError(f"metric {name!r}: source={data['source']!r}")
    if "workloads" in data:
        missing = sorted(set(data["workloads"]) - set(names("workloads")))
        if missing:
            raise RegistryError(
                f"metric {name!r} names cells that do not exist: {missing}")
    return data


def code(kind: str, name: str):
    """The module perf/<kind>/<name>.py (a generator, runner, reader or
    family)."""
    if not NAME_RE.match(name) or not os.path.isfile(
            os.path.join(ROOT, kind, name + ".py")):
        have = ", ".join(names(kind)) or "none"
        raise RegistryError(f"no perf/{kind}/{name}.py (have: {have})")
    return importlib.import_module(f"perf.{kind}.{name}")


def family(cfg: Dict[str, Any]):
    """The module perf/families/<family>.py a configuration names: the one
    place its ``family`` key is resolved."""
    if "family" not in cfg:
        have = ", ".join(names("families")) or "none"
        raise RegistryError(
            f"configuration {cfg.get('name')!r} names no family (have: {have})")
    return code("families", cfg["family"])


def workload(name: str) -> Dict[str, Any]:
    """One cell, resolved: its configuration, traffic mix and metric files
    attached, and every cross-reference checked. Its per-layer metrics are
    those its own file lists, in the file's order, then every metric whose
    file names the cell under ``workloads``, in sorted order: a later PR
    adds a metric to a standing cell by the metric's files alone."""
    cell = _load("workloads", name)
    for key in ("config", "traffic", "chips", "runner", "why",
                "end_to_end", "per_layer"):
        if key not in cell:
            raise RegistryError(f"workload {name!r} lacks {key!r}")
    if cell["chips"] not in (1, 4):
        raise RegistryError(f"workload {name!r}: chips={cell['chips']!r}")
    cell["config_file"] = config(cell["config"])
    cell["traffic_file"] = traffic(cell["traffic"])
    code("runners", cell["runner"])
    code("generators", cell["traffic_file"]["generator"])
    e2e = {m: metric(m) for m in cell["end_to_end"]}
    layer = {m: metric(m) for m in cell["per_layer"]}
    for m in names("metrics"):
        if m not in layer:
            spec = metric(m)
            if name in spec.get("workloads", ()):
                layer[m] = spec
    cell["per_layer"] = list(layer)
    if "setup_s" not in e2e or len(e2e) < 2:
        raise RegistryError(
            f"workload {name!r} must report setup_s and one more "
            f"end-to-end metric")
    if not layer:
        raise RegistryError(f"workload {name!r} reports no per-layer metric")
    for m, spec in e2e.items():
        if spec["source"] not in ("host_clock", "device_trace"):
            raise RegistryError(
                f"end-to-end metric {m!r} has source {spec['source']!r}")
    for m, spec in layer.items():
        for key in ("layer", "moves", "reader"):
            if key not in spec:
                raise RegistryError(f"per-layer metric {m!r} lacks {key!r}")
        if spec["moves"] not in e2e:
            raise RegistryError(
                f"workload {name!r} reports {m!r}, which moves "
                f"{spec['moves']!r}, but does not report {spec['moves']!r}")
        code("readers", spec["reader"])
    cell["end_to_end_specs"] = e2e
    cell["per_layer_specs"] = layer
    return cell


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(ROOT, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise RegistryError(
            f"device kind {device_kind!r} is not in perf/peaks.json "
            f"(has: {sorted(table)}); add it with its source, do not "
            f"default it")
    return table[device_kind]
