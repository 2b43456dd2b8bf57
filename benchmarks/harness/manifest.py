"""``BENCHMARK.json`` and the data files it names.

A cell ``<config>.<traffic>`` resolves, by name alone, to
``configs/<config>.json``, ``traffic/<traffic>.json`` and one
``metrics/<metric>.json`` per per-layer metric the cell reports.  Adding
a configuration, a mix or a metric is adding files and manifest entries;
nothing here is edited.  This module never imports JAX.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _read(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    end_to_end: list        # manifest entries this cell reports
    per_layer: list         # manifest entries merged over metrics/<name>.json


class Manifest:
    def __init__(self, root):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.data = _read(os.path.join(root, "BENCHMARK.json"))

    def data_file(self, kind, name):
        return os.path.join(self.bench_dir, kind, name + ".json")

    def metric(self, entry):
        """The manifest's entry over the metric's own file (reader,
        arguments); where both give a key they must agree."""
        spec = _read(self.data_file("metrics", entry["name"]))
        for key in ("unit", "layer", "moves", "source", "better"):
            if key in spec and spec[key] != entry[key]:
                raise ValueError(f"{entry['name']}: {key} differs between "
                                 "BENCHMARK.json and the metric's file")
        return {**spec, **entry}

    def cell(self, name):
        w = next((w for w in self.data["workloads"] if w["name"] == name),
                 None)
        if w is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        here = lambda m: name in m.get("workloads", [name])   # noqa: E731
        return Cell(
            name=name, chips=w["chips"],
            config=_read(self.data_file("configs", w["config"])),
            traffic=_read(self.data_file("traffic", w["traffic"])),
            end_to_end=[m for m in self.data["end_to_end"] if here(m)],
            per_layer=[self.metric(m) for m in self.data["per_layer"]
                       if here(m)])


def with_rehearsal(spec):
    """``spec`` with its ``rehearsal`` group laid over it: the tiny sizes a
    CPU run uses, which the manifest does not expose."""
    out = {k: v for k, v in spec.items() if k != "rehearsal"}
    for k, v in spec.get("rehearsal", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out
