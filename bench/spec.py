"""Everything a cell is made of, found by name under a root directory.

``BENCHMARK.json`` names the cells, configurations and metrics.  A
configuration is the JSON file its entry names; a traffic mix is
``bench/traffic/<traffic>.json``; a metric is read by
``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number or
``None`` when the run has nothing for it to read.  A new cell, mix or
metric is new files and entries: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib


def load(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve(root: pathlib.Path, workload: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell entry, configuration, traffic mix) of one cell."""
    spec = load(root)
    cell = _named(spec["workloads"], workload, "workload")
    entry = _named(spec["configs"], cell["config"], "configuration")
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    if mix["system"] != config["system"]:
        raise ValueError(f"traffic {cell['traffic']!r} drives a "
                         f"{mix['system']}, configuration {cell['config']!r} "
                         f"is a {config['system']}")
    return spec, cell, config, mix


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those without a list whose end-to-end metric it
    reports."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def reader(root: pathlib.Path, metric: str):
    """``read`` of ``bench/metrics/<metric>.py`` under ``root``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(root: pathlib.Path, device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` from
    ``bench/peaks.json``; a kind the table lacks is an error, never a
    default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
