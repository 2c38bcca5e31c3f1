"""What a run is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's
file is the one ``configs[].file`` gives, a traffic mix is
``<path>/traffic/<traffic>.json`` and a per-layer metric
``<path>/metrics/<name>.json`` under one of ``paths``. A metric's file
names its reader, ``<path>/readers/<reader>.py``. A later PR adds
files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # metric entries of BENCHMARK.json for this cell
    per_layer: list      # the same, each with its file's content merged
    roots: list          # directories searched for data files and readers


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _find(roots: list, *parts: str) -> str:
    for root in roots:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {roots}")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark_json: "str | None" = None) -> Cell:
    benchmark_json = benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
    base = os.path.dirname(os.path.abspath(benchmark_json))
    bench = _load(benchmark_json)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {benchmark_json}; "
                         f"it has {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    roots = [os.path.join(base, p) for p in bench["paths"]]
    if HERE not in roots:
        roots.append(HERE)  # the readers and references of this tree
    config = _load(os.path.join(base, configs[entry["config"]]["file"]))
    traffic = _load(_find(roots, "traffic", entry["traffic"] + ".json"))
    per_layer = [
        {**_load(_find(roots, "metrics", m["name"] + ".json")), **m}
        for m in bench["per_layer"] if _in_cell(m, workload)]
    end_to_end = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    return Cell(workload, int(entry["chips"]), config, traffic, end_to_end,
                per_layer, roots)


def load_module(roots: list, kind: str, name: str):
    """``<root>/<kind>/<name>.py``, from the first root that has it."""
    path = _find(roots, kind, name + ".py")
    if os.path.dirname(os.path.dirname(path)) == HERE:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    module_spec = importlib.util.spec_from_file_location(
        f"benchmark_added.{kind}.{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def resolve(dotted: str):
    """``package.module.attribute`` -> the attribute."""
    module, _, attribute = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attribute)


def rehearsed(block: dict, rehearse: bool) -> dict:
    """A data file's content with its ``rehearsal`` block laid over it
    (recursively for nested groups) when rehearsing on the CPU."""
    out = {k: v for k, v in block.items() if k != "rehearsal"}
    if rehearse:
        for key, value in block.get("rehearsal", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def model_numbers(config: dict) -> dict:
    """The configuration's numbers under their Hugging Face keys: what
    the plain reference and the counts from shapes are given."""
    return {k: v for k, v in config.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def build_model_config(config: dict):
    """The program's own model configuration object, built by the dotted
    path the file names from the file's Hugging Face keys."""
    builder = config["builder"]
    kwargs = {ours: config[theirs]
              for ours, theirs in builder["from_keys"].items()}
    kwargs.update(builder.get("kwargs", {}))
    return resolve(builder["path"])(**kwargs)
