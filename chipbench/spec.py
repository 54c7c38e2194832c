"""What a run is made of, read from data files found by name.

``BENCHMARK.json`` (repo root) names the cells, the configurations and
the metrics. Everything that belongs to one of them sits in a file of its
own under ``chipbench/``:

    configs/<config>.json         sizes, source, what was cut or assumed;
                                  may name its `world`, `reference`, `step_flops`
    traffic/<traffic>.json        one traffic mix: parameters of a generator
    cells/<cell>.json             config + traffic + entry + the limits of `correct`
    layer_metrics/<metric>.json   which reducer reads the metric, with what

and so does the code that belongs to one of them (``plugin`` below):

    entries/<entry>.py            ``Run``: the program a cell's window drives
    worlds/<world>.py             ``make``: the run's arrays, on the device
    references/<reference>.py     the model's part of the plain reference
    reducers/<reducer>.py         ``reduce``: one per-layer metric's reduction
    work/<work>.py                ``work``: bytes and FLOPs from a cell's shapes

so a later PR adds a cell, a metric or a whole deployment by adding files
and entries. Every look-up goes through ``SEARCH``, first hit wins.
"""

from __future__ import annotations

import importlib.util
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


PLUGIN_KINDS = ("entries", "worlds", "references", "reducers", "work")
# what a configuration gets for a key it does not state (every
# configuration from before these keys existed reads as it did)
DEFAULTS = {"world": "planted", "reference": "sage",
            "step_flops": "sage_matmul"}
_plugins = {}                       # absolute path -> loaded module


def plugin_files(kind: str) -> dict:
    """``{name: path}`` of the ``<kind>/<name>.py`` files under ``SEARCH``
    (for the error's list and the tests); the first directory that holds a
    name wins."""
    if kind not in PLUGIN_KINDS:
        raise ValueError(f"chipbench: no such kind of file: {kind!r}")
    found = {}
    for base in SEARCH:
        folder = os.path.join(base, kind)
        if not os.path.isdir(folder):
            continue
        for f in sorted(os.listdir(folder)):
            if f.endswith(".py") and not f.startswith("_"):
                found.setdefault(f[:-len(".py")], os.path.join(folder, f))
    return found


def plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py``, found by name as the data files
    are and loaded from its path (once a path). An unknown name is an
    error that lists what was found."""
    for base in SEARCH:
        path = os.path.abspath(os.path.join(base, kind, name + ".py"))
        if os.path.exists(path):
            break
    else:
        raise SystemExit(
            f"chipbench: no file {kind}/{name}.py under {SEARCH} "
            f"(found: {sorted(plugin_files(kind))})")
    if path not in _plugins:
        found = importlib.util.spec_from_file_location(
            f"chipbench.{kind}.{name}", path)
        module = importlib.util.module_from_spec(found)
        found.loader.exec_module(module)
        _plugins[path] = module
    return _plugins[path]


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
    def reference(self):
        """The model's part of the plain reference, by the configuration's
        ``reference``."""
        return plugin("references", self.named("reference"))

    def named(self, key: str) -> str:
        """The configuration's ``world``, ``reference`` or ``step_flops``."""
        return self.config.get(key, DEFAULTS[key])

    @property
    def batch(self) -> int:
        """Seeds one execution of the cell's program takes: the mix's
        ``batch``, or the server's ``batch_cap``."""
        return int(self.traffic["batch"] if "batch" in self.traffic
                   else self.cell["server"]["batch_cap"])

    @property
    def dims(self):
        c = self.config
        return ([c["feature_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
                + [c["num_classes"]])
