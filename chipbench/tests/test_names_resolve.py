"""Every name the benchmark's data uses resolves to a file: the ``entry``
of each cell, the ``world``, ``reference`` and ``step_flops`` of each
configuration, the ``reducer`` and ``work`` of each per-layer metric, for
``BENCHMARK.json`` and for the tests' ``tiny`` and ``outsider`` copies; an
unknown name raises the error that lists what was found. No jit, under a
second. (ISSUE 27 asked for it under ``tests/``; a ``benchmark`` PR adds
files under the benchmark's own directories only.)"""

import glob
import json
import os

import pytest

from chipbench import readers, spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOTS = {"repo": (None, spec.BENCHMARK_FILE),
         "tiny": (os.path.join(HERE, "tiny"),) * 2,
         "outsider": (os.path.join(HERE, "outsider"),) * 2}
INTERFACE = {"entries": "Run", "worlds": "make", "reducers": "reduce",
             "work": "work", "references": "init_layers"}


@pytest.fixture(params=sorted(ROOTS))
def root(request, monkeypatch):
    front, bench = ROOTS[request.param]
    if front:
        monkeypatch.setattr(spec, "SEARCH", [front] + spec.SEARCH)
        monkeypatch.setattr(spec, "BENCHMARK_FILE",
                            os.path.join(bench, "BENCHMARK.json"))
    return spec.SEARCH[0]


def _has(kind, name):
    assert name in spec.plugin_files(kind), (kind, name)
    assert callable(getattr(spec.plugin(kind, name), INTERFACE[kind]))


def test_every_name_of_the_benchmark_resolves(root):
    bench = spec.benchmark()
    assert bench["workloads"]
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        _has("entries", cell.entry)
        _has("worlds", cell.named("world"))
        _has("references", cell.named("reference"))
        assert cell.reference is spec.plugin("references",
                                             cell.named("reference"))
        _has("work", cell.named("step_flops"))
        for m in cell.per_layer:
            assert callable(readers.reducer(m["reducer"])), m["name"]
            if "work" in m.get("args", {}):
                _has("work", m["args"]["work"])


def test_every_data_file_resolves_too(root):
    """Also the files no cell of ``BENCHMARK.json`` uses yet."""
    for path in glob.glob(os.path.join(root, "cells", "*.json")):
        _has("entries", json.load(open(path))["entry"])
    for path in glob.glob(os.path.join(root, "configs", "*.json")):
        cfg = json.load(open(path))
        for key, kind in (("world", "worlds"), ("reference", "references"),
                          ("step_flops", "work")):
            _has(kind, cfg.get(key, spec.DEFAULTS[key]))
    for path in glob.glob(os.path.join(root, "layer_metrics", "*.json")):
        m = json.load(open(path))
        assert callable(readers.reducer(m["reducer"])), path
        if "work" in m.get("args", {}):
            _has("work", m["args"]["work"])


def test_the_configurations_name_what_they_use():
    """The real and the tiny configuration state their world, reference
    and FLOP count by name, and the names are the defaults: a
    configuration from before PR 27, without the keys, reads the same."""
    for path in (os.path.join(spec.HERE, "configs", "papers100m-sage-1of8.json"),
                 os.path.join(HERE, "tiny", "configs", "tiny-sage.json")):
        cfg = json.load(open(path))
        assert {k: cfg[k] for k in spec.DEFAULTS} == spec.DEFAULTS


@pytest.mark.parametrize("kind", spec.PLUGIN_KINDS)
def test_an_unknown_name_is_an_error_that_lists_the_files(kind):
    with pytest.raises(SystemExit) as e:
        spec.plugin(kind, "no_such_thing")
    said = str(e.value)
    assert f"no file {kind}/no_such_thing.py" in said
    assert all(name in said for name in spec.plugin_files(kind))
    assert spec.plugin_files(kind), kind        # every directory holds one


def test_an_unknown_reducer_and_kind():
    with pytest.raises(SystemExit):
        readers.reducer("no_such_reducer")
    with pytest.raises(ValueError):
        spec.plugin_files("kernels")
