"""Find a cell and everything that belongs to it by name.

A cell is one entry of `workloads` in BENCHMARK.json. Its configuration is
the file its `configs` entry names, its traffic mix is
`traffic/<traffic>.json`, a metric is `metrics/<name>.json`, and the code
pieces (readers, data generators, query makers, references) are modules
found by the name those files give. Adding a cell, a mix, a configuration
with a new op, a metric or a reader adds files and entries and edits
nothing that is there.

`root` is the directory that holds BENCHMARK.json; everything is found
under it, so a test can drive a temporary copy.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

BENCH_DIR = "benchmarks"


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries (BENCHMARK.json) + their files
    per_layer: list


def _applies(metric: dict, cell_name: str) -> bool:
    """The manifest's optional `workloads` key on a metric: the cells that
    report it (the builder's contract); without it, every cell does."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, cell_name: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == cell_name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    here = os.path.join(root, BENCH_DIR)

    def with_spec(group: str) -> list:
        out = []
        for m in bench[group]:
            if _applies(m, cell_name):
                spec = load_json(os.path.join(here, "metrics",
                                              m["name"] + ".json"))
                out.append({**m, "spec": spec})
        return out

    return Cell(
        root=root, name=cell_name, chips=int(entry["chips"]),
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(here, "traffic",
                                       entry["traffic"] + ".json")),
        end_to_end=with_spec("end_to_end"), per_layer=with_spec("per_layer"))


@functools.lru_cache(maxsize=None)
def plugin(root: str, kind: str, name: str):
    """The module `<root>/benchmarks/<kind>/<name>.py`, for kind in
    readers / datagen / queries / reference, loaded by its path."""
    path = os.path.join(root, BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
