"""Finds a cell's pieces by name. Nothing here knows a cell, a
configuration, a traffic mix or a metric: each is a file found from
the names in BENCHMARK.json, so a later change adds cells by adding
files.

  <root>/BENCHMARK.json                 cells, configurations, metrics
  <root>/<configs[].file>               sizes as run, source, cut
  <bench>/cells/<workload>.json         budget, lanes, limits
  <bench>/traffic/<traffic>.json        loop kind, laws, rate
  <bench>/metrics/<metric>.py           read(run) -> number or None
  <bench>/references/<reference>.py     the configuration's weight layout
                                        and plain reference
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

# Serving settings every cell shares: the Pallas kernels, the TRIM-KV
# policy, 512-position prompt chunks, 16-step decode segments,
# interleaved admission and a queue that never refuses. A cell file
# gives only the budget M and the number of lanes.
SERVE = {"attn_impl": "pallas", "policy": "trimkv", "prefill_chunk": 512,
         "decode_segment": 16, "interleaved": True, "max_queue": 1 << 30}


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: str
    bench: str
    workload: dict
    config: dict          # the configuration file
    cell: dict            # the cell file
    traffic: dict         # the traffic file
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def serve(self) -> dict:
        """The serving settings as run: SERVE with the cell's budget
        and lanes."""
        return {**SERVE, "budget": self.cell["budget"],
                "lanes": self.cell["lanes"]}

    def metric(self, name: str):
        return load_module(os.path.join(self.bench, "metrics", name + ".py"),
                           "metric_" + name.replace(".", "_"))

    def reference(self):
        ref = self.config["reference"]
        return load_module(os.path.join(self.bench, "references",
                                        ref + ".py"), "reference_" + ref)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: str, bench: str, workload: str) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    return Cell(
        root=root, bench=bench, workload=w, config=config,
        cell=_json(os.path.join(bench, "cells", workload + ".json")),
        traffic=_json(os.path.join(bench, "traffic",
                                   w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])
