"""The per-layer metrics as data: every ``layer_metrics/*.json`` names a
reducer that exists and sits under the end-to-end metric its suffix
says; the counter metrics read the server's own ``snapshot()["serving"]``
of a tiny run (``tiny/BENCHMARK.json`` enters them under the tiny cells); the device-scope metrics read a pair of small traces
recorded on the chip (PR 25: three steps of a 200,000-node two-hop train
step, batch 256, and three batches of the serve step over the same graph
through ``MicroBatchServer``, each with its compiled text).

A reducer that finds nothing drops its metric from the line in silence,
so without these a misspelt scope or counter would pass every other test.
"""

import glob
import gzip
import json
import os

import pytest

from chipbench import readers, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
SUFFIX = {"train": "train_seeds_per_s", "tail": "serve_p95_ms",
          "rate": "serve_req_per_s"}
METRIC_FILES = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(spec.HERE, "layer_metrics", "*.json")))
COUNTER_METRICS = {
    "tiny-steady": ["queue_wait_mean_ms.tail", "coalesce_ms.tail",
                    "pipeline_wait_ms.tail", "execute_ms.tail"],
    "tiny-flood": ["pipeline_wait_ms.rate", "put_ms.rate", "get_ms.rate",
                   "scatter_ms.rate"]}
TRAIN, STEADY = "papers100m-sage-train", "papers100m-sage-serve-steady"


@pytest.mark.parametrize("name", METRIC_FILES)
def test_metric_file_names_a_reducer_and_its_suffix_agrees(name):
    with open(os.path.join(spec.HERE, "layer_metrics", name + ".json")) as f:
        m = json.load(f)
    assert callable(readers.reducer(m["reducer"]))
    assert m["reads"]
    tiny = os.path.join(HERE, "tiny", "BENCHMARK.json")
    entries = [e for path in (spec.BENCHMARK_FILE, tiny)
               for e in json.load(open(path))["per_layer"] if e["name"] == name]
    # collective_ms.train waits for its four-chip cell: tiny only
    assert entries, f"no per_layer entry reads layer_metrics/{name}.json"
    for e in entries:
        assert e["moves"] == SUFFIX[name.rsplit(".", 1)[1]]
        assert e["workloads"]


class _NoDevice:
    """A traced run's trace where no device plane was found."""
    window_s = busy_s = 0.0

    def seconds(self, pick):
        return None


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_metrics_read_the_servers_snapshot(tiny, name):
    import jax
    cell = spec.Cell(name)
    run = spec.plugin("entries", cell.entry).Run(cell, 2**31 + 5,
                                                 jax.devices())
    run.setup()
    win = run.window(0.5)
    counters = run.stop()
    got = readers.read_all({
        "trace": _NoDevice(), "facts": {"batches": win["batches"]},
        "counters": counters, "cell": cell, "peaks": None, "chips": 1})
    for metric in COUNTER_METRICS[name]:
        assert got[metric]["value"] > 0 and got[metric]["unit"] == "ms", metric
    ms = lambda key, over: 1e3 * counters[key] / counters[over]
    if name == "tiny-steady":
        assert got["coalesce_ms.tail"]["value"] == ms("coalesce_s", "batches")
        assert got["queue_wait_mean_ms.tail"]["value"] == \
            ms("queue_wait_s", "completed")
        # a request waits at least through its batch's pipeline wait
        assert got["queue_wait_mean_ms.tail"]["value"] > \
            0.5 * got["pipeline_wait_ms.tail"]["value"]
    else:
        inner = sum(counters[k] for k in ("put_s", "launch_s", "get_s",
                                          "scatter_s"))
        assert 0 < inner <= counters["execute_s"]


def _recorded(which, cell_name, facts):
    hlo = gzip.open(os.path.join(
        HERE, f"recorded_scopes_{which}_hlo.txt.gz"), "rt").read()
    tr = trace.Trace(os.path.join(HERE, f"recorded_scopes_{which}.xplane.pb"),
                     trace.scopes_of(hlo), chips=1)
    got = readers.read_all({
        "trace": tr, "facts": facts, "counters": None,
        "cell": spec.Cell(cell_name), "peaks": spec.peaks("TPU v5 lite"),
        "chips": 1})
    return tr, {k: v["value"] for k, v in got.items()}


@pytest.fixture(scope="module")
def facts():
    with open(os.path.join(HERE, "recorded_scopes_facts.json")) as f:
        return json.load(f)


def test_recorded_train_scopes(facts):
    tr, got = _recorded("train", TRAIN, {"steps": facts["steps"]})
    assert tr.busy_s > 0 and len(tr.devices[0]) > 500
    draw, compact = got["draw_ms.train"], got["compact_ms.train"]
    # the two halves of a hop are all of it
    assert draw > 0 and compact > 0
    assert draw + compact == pytest.approx(got["sample_ms.train"], rel=1e-6)
    assert got["gather_ms.train"] > 0
    fwd, back = got["forward_ms.train"], got["backward_ms.train"]
    assert fwd > 0 and back > 0
    both = readers.scope_ms({"trace": tr, "facts": facts}, "qt_forward",
                            "steps")
    # no op of the backward pass is counted as forward, none is lost
    assert fwd + back == pytest.approx(both, rel=1e-6)
    named = readers.scope_ms(
        {"trace": tr, "facts": facts},
        r"qt_(sample_hop\d|gather|forward|loss|optimizer)", "steps")
    assert named > 0.85 * 1e3 * tr.busy_s / facts["steps"]
    top = [k for k, _ in tr.top_ops()]
    assert "qt_gather" in top and "qt_forward" in top
    assert not any("jvp(GraphSAGE)" in k for k in top)


def test_scope_roofline_on_the_recorded_trace(facts):
    """``scope_roofline`` over ``qt_gather`` with the frontier gather's bytes
    from the recorded step's shapes: the same least time as
    ``gather_roofline`` takes over a longer one (the scope also holds the
    mask multiply), so a little under it, and far under 100 %."""
    import types
    cfg = facts["cfg"]
    cell = types.SimpleNamespace(config=cfg, batch=facts["batch"])
    tr, _ = _recorded("train", TRAIN, {"steps": facts["steps"]})
    ctx = {"trace": tr, "facts": {"steps": facts["steps"]}, "cell": cell,
           "peaks": spec.peaks("TPU v5 lite")}
    by_scope = readers.reducer("scope_roofline")(
        ctx, pattern="qt_gather", per="steps", work="frontier_gather",
        peak="hbm_bytes_per_s")
    by_shape = readers.gather_roofline(
        ctx, operand="f32[{nodes},{dim}]", result="f32[{frontier},{dim}]",
        per="steps")
    assert 0 < by_scope < by_shape < 100
    rows = 256 * 11 * 6
    least = rows * (2 * 64 * 4 + 4) / 819e9
    assert by_scope == pytest.approx(
        100 * least / (1e-3 * readers.scope_ms(ctx, "qt_gather", "steps")))
    with pytest.raises(ValueError):
        readers.reducer("scope_roofline")(
            ctx, pattern="qt_gather", per="steps", work="frontier_gather",
            peak="int8_ops_per_s")


def test_recorded_serve_scopes(facts):
    tr, got = _recorded("serve", STEADY, {"batches": facts["steps"]})
    assert got["compact_share.tail"] > 0 and got["gather_share.tail"] > 0
    draw = readers.scope_share({"trace": tr}, "qt_draw")
    assert draw + got["compact_share.tail"] == \
        pytest.approx(got["sample_share.tail"], rel=1e-6)
    assert got["forward_ms.tail"] > 0                    # qt_serve_forward
    assert "qt_gather" in [k for k, _ in tr.top_ops()]
    # the host stages are on the profiler's clock, inside the window
    inside = [h[1] for h in tr.host if h[2] >= tr.t0 and h[3] <= tr.t1]
    for stage in ("serve.batch_coalesce", "serve.pipe_submit",
                  "serve.dispatch", "serve.put", "serve.launch", "serve.get",
                  "serve.scatter"):
        assert inside.count(stage) == facts["steps"], stage
    assert inside.count("pipeline.idle") >= facts["steps"] - 1
