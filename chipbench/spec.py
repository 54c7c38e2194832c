"""What a run is made of, read from data files found by name.

``BENCHMARK.json`` (repo root) names the cells, the configurations and
the metrics. Everything that belongs to one of them sits in a file of its
own under ``chipbench/``:

    configs/<config>.json         sizes, source, what was cut or assumed
    traffic/<traffic>.json        one traffic mix: parameters of a generator
    cells/<cell>.json             config + traffic + entry + the limits of `correct`
    layer_metrics/<metric>.json   which reducer reads the metric, with what

so a later PR adds a cell or a metric by adding files and entries.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the tests put their tiny cells' directory in front, and their own
# BENCHMARK.json in place; no command line reaches either
SEARCH = [HERE]
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")


def _load(*parts):
    for base in SEARCH:
        path = os.path.join(base, *parts)
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise SystemExit(f"chipbench: no file {os.path.join(*parts)} under "
                     f"{SEARCH}")


def benchmark() -> dict:
    with open(BENCHMARK_FILE) as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    table = _load("peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"chipbench: no peaks for device_kind {device_kind!r} in "
            "chipbench/peaks.json; an unknown device is an error, not a default")
    return table[device_kind]


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, name: str):
        bench = benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(
                f"chipbench: no workload {name!r} in BENCHMARK.json "
                f"(has: {[w['name'] for w in bench['workloads']]})")
        self.name = name
        self.chips = int(entry["chips"])
        self.cell = _load("cells", name + ".json")
        self.config = _load("configs", entry["config"] + ".json")
        self.traffic = _load("traffic", entry["traffic"] + ".json")
        for key, want in (("config", entry["config"]),
                          ("traffic", entry["traffic"]), ("chips", self.chips)):
            if self.cell[key] != want:
                raise SystemExit(
                    f"chipbench: cells/{name}.json says {key}="
                    f"{self.cell[key]!r}, BENCHMARK.json says {want!r}")
        self.entry = self.cell["entry"]
        self.limits = self.cell["limits"]
        applies = lambda m: name in m.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [dict(m, **_load("layer_metrics", m["name"] + ".json"))
                          for m in bench["per_layer"] if applies(m)]

    @property
    def dims(self):
        c = self.config
        return ([c["feature_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
                + [c["num_classes"]])
