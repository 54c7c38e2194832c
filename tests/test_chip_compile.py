"""Every Pallas kernel of ``quiver_tpu/ops/pallas`` compiles for the chip.

No chip is needed: the TPU's compiler is installed, and it compiles for a
``v5e:2x2`` device that is described, not attached
(``jax.experimental.topologies``). What it refuses here (a block shape,
an unaligned DMA slice, too much scratch) the chip would refuse too, and
interpret mode never sees it. Sizes are ogbn-products': 2,449,029 nodes,
123,718,280 edge slots, float32 features, batch 1024, ``row_cap`` 2048,
the on-core PRNG, ``interpret=False``.

All of them live in this ONE file: only one process at a time can load
the TPU's library, so the topology is described inside a module-scoped
fixture, by the one worker this file is given to, and nothing touches it
at import. A compile for a described device is written to the persistent
cache but cannot be read back without a chip, so the cache is off around
these tests.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from quiver_tpu.ops.pallas import _dma, fused, gather, sample_kernel

NODES = 2_449_029
EDGES = 123_718_280
BATCH = 1024
ROW_CAP = 2048
K = 15


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """``shape_on_chip(shape, dtype)``: an abstract array on the first
    described chip, with the persistent compile cache off while the
    module's tests run."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def graph(shape_on_chip):
    """(indptr, indices_padded, seeds, kernel seed, key data) shapes."""
    padded = jax.eval_shape(
        lambda x: _dma.pad_indices(x, ROW_CAP),
        jax.ShapeDtypeStruct((EDGES,), jnp.int32))
    return (shape_on_chip((NODES + 1,), jnp.int32),
            shape_on_chip(padded.shape, padded.dtype),
            shape_on_chip((BATCH,), jnp.int32),
            shape_on_chip((), jnp.int32),
            shape_on_chip((2,), jnp.uint32))


def _kernels_in(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("dim", [100, 128])
def test_fused_hot_hop(graph, shape_on_chip, dim):
    indptr, indices, seeds, seed, _ = graph
    feat = shape_on_chip((NODES, dim), jnp.float32)

    def fn(indptr, indices, seeds, feat, seed):
        return fused.fused_hot_hop(indptr, indices, seeds, feat, K, seed,
                                   row_cap=ROW_CAP, rng="tpu",
                                   interpret=False)

    # width 100 pads the table to 128 lanes (with its trace-time warning)
    with pytest.warns(UserWarning) if dim % 128 else contextlib.nullcontext():
        assert _kernels_in(fn, indptr, indices, seeds, feat, seed) == 1


def test_fused_hot_hop_feature_order(graph, shape_on_chip):
    """The tiered serve path: old id -> storage row translated in-kernel,
    rows past ``hot_rows`` masked."""
    indptr, indices, seeds, seed, _ = graph
    hot = NODES // 5
    feat = shape_on_chip((hot, 128), jnp.float32)
    order = shape_on_chip((NODES,), jnp.int32)

    def fn(indptr, indices, seeds, feat, seed, order):
        return fused.fused_hot_hop(indptr, indices, seeds, feat, K, seed,
                                   row_cap=ROW_CAP, rng="tpu",
                                   interpret=False, feature_order=order,
                                   hot_rows=hot)

    assert _kernels_in(fn, indptr, indices, seeds, feat, seed, order) == 1


def test_fused_hot_hop_refuses_int8_table_uncompiled(graph):
    """The one kernel variant the chip's compiler refuses (a per-row DMA
    out of an int8 table, four rows to a sublane): it raises with the
    compiler's reason and falls back to nothing."""
    from quiver_tpu.ops import quant
    indptr, indices, seeds, seed, _ = graph
    feat = jax.eval_shape(lambda x: quant.quantize(x, "int8"),
                          jax.ShapeDtypeStruct((4096, 128), jnp.float32))

    def fn(indptr, indices, seeds, feat, seed):
        return fused.fused_hot_hop(indptr, indices, seeds, feat, K, seed,
                                   row_cap=ROW_CAP, rng="tpu",
                                   interpret=False)

    with pytest.raises(NotImplementedError, match="aligned to tiling"):
        jax.jit(fn).lower(indptr, indices, seeds, feat, seed)


def test_fused_sample_hop(graph):
    indptr, indices, seeds, seed, _ = graph

    def fn(indptr, indices, seeds, seed):
        return fused.fused_sample_hop(indptr, indices, seeds, K, seed,
                                      row_cap=ROW_CAP, rng="tpu",
                                      interpret=False)

    assert _kernels_in(fn, indptr, indices, seeds, seed) == 1


def test_fused_multihop(graph, shape_on_chip):
    """The whole [15, 10, 5] walk as one program: two sampling-only hops
    and the sample+gather leaf. Batch 128, not 1024: the kernels' blocks
    are the same, and the XLA sorts between the hops (which no test here
    is about) compile in a sixth of the time."""
    indptr, indices, _, _, key = graph
    seeds = shape_on_chip((128,), jnp.int32)
    feat = shape_on_chip((NODES, 128), jnp.float32)

    def fn(indptr, indices, seeds, feat, key):
        return fused.fused_multihop(
            indptr, indices, seeds, feat, (15, 10, 5),
            jax.random.wrap_key_data(key), row_cap=ROW_CAP, rng="tpu",
            interpret=False)

    assert _kernels_in(fn, indptr, indices, seeds, feat, key) == 3


def test_sample_layer_pallas(graph):
    indptr, indices, seeds, seed, _ = graph

    def fn(indptr, indices, seeds, seed):
        return sample_kernel.sample_layer_pallas(
            indptr, indices, seeds, K, seed, row_cap=ROW_CAP, rng="tpu",
            interpret=False)

    assert _kernels_in(fn, indptr, indices, seeds, seed) == 1


@pytest.mark.parametrize("dim", [100, 128])
def test_gather_rows(graph, shape_on_chip, dim):
    _, _, seeds, _, _ = graph
    feat = shape_on_chip((NODES, dim), jnp.float32)
    # width 100 pads the table to 128 lanes (with its trace-time warning)
    with pytest.warns(UserWarning) if dim % 128 else contextlib.nullcontext():
        assert _kernels_in(
            lambda feat, ids: gather.gather_rows(feat, ids, interpret=False),
            feat, seeds) == 1


def _sage_state(dims, tx):
    """GraphSAGE's train state, by the shapes of its parameter tree."""
    from quiver_tpu.parallel.train import TrainState
    params = {"params": {f"conv{i}": {
        "lin_root": {"kernel": jnp.zeros((a, b)), "bias": jnp.zeros((b,))},
        "lin_nbr": {"kernel": jnp.zeros((a, b))}}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}}
    return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))


def test_dist_step_temporaries_are_bounded_by_the_exchange_cap(topo):
    """The row-sharded papers100M step (``build_dist_train_step`` at the
    shapes of the cell ``papers100m-sage-train-dist4``: 55.5 M nodes, 808 M
    edge slots, 13.9 M rows of 512 B a chip, 1024 seeds a chip, the cell's
    ``exchange_cap``) compiles for the four described chips, fits one, and
    no branch of it holds a block of the frontier's size a chip
    (``[4, 1081344, 128]`` float32 = 2.2 GB: the dense exchange holds two)."""
    import json
    import os

    import numpy as np
    import optax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel.dist import build_dist_train_step
    from quiver_tpu.pyg.sage_sampler import layer_shapes

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")
    with open(os.path.join(bench, "configs",
                           "papers100m-sage-4of8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "cells",
                           "papers100m-sage-train-dist4.json")) as f:
        cell = json.load(f)
    chips, batch, sizes = cell["chips"], 1024, cfg["fanout"]
    nodes, edges, dim = cfg["nodes"], cfg["edges"], cfg["feature_dim"]
    rows = -(-nodes // chips)
    frontier = layer_shapes(batch, sizes)[-1].n_id_cap
    assert frontier == 1_081_344 and cell["exchange_cap"] < frontier // 4

    mesh = Mesh(np.array(topo.devices[:chips]), ("host",))
    on = lambda spec: NamedSharding(mesh, spec)
    arr = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=on(spec))
    model = GraphSAGE(hidden_dim=cfg["hidden_dim"],
                      out_dim=cfg["num_classes"],
                      num_layers=cfg["num_layers"], dropout=cfg["dropout"])
    tx = optax.adam(cfg["optimizer"]["learning_rate"])

    dims = [dim] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1) \
        + [cfg["num_classes"]]
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.tree.map(lambda a: arr(a.shape, a.dtype, P()),
                         jax.eval_shape(lambda: _sage_state(dims, tx)))
    step = build_dist_train_step(model, tx, sizes, batch, mesh,
                                 rows_per_host=rows,
                                 exchange_cap=cell["exchange_cap"],
                                 collect_metrics=True)
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul"])
    compilation_cache.reset_cache()
    try:
        compiled = step.jitted_fns[-1].lower(
            state, arr((chips * rows, dim), jnp.float32, P("host", None)),
            arr((nodes,), jnp.int32, P()), arr((nodes,), jnp.int32, P()),
            arr((nodes + 1,), jnp.int32, P()), arr((edges,), jnp.int32, P()),
            arr((chips * batch,), jnp.int32, P("host")),
            arr((chips * batch,), jnp.int32, P("host")),
            arr(key.shape, key.dtype, P())).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_default_matmul_precision", was[1])
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3e9, memory
    assert memory.argument_size_in_bytes \
        + memory.temp_size_in_bytes < 15e9, memory
    text = compiled.as_text()
    assert f"f32[{chips},{frontier},{dim}]" not in text
    assert f"f32[{chips},{cell['exchange_cap']},{dim}]" in text


def _loop_bounds(text):
    """What each ``while`` of a compiled program compares its counter
    with: the operands of its condition's ROOT."""
    import re
    bounds = []
    for cond in re.findall(r" while\(.*?condition=(%[\w.]+)", text):
        body = text[text.index(f"\n{cond} "):]
        root = re.search(r"ROOT [^\n]*compare\(([^)]*)\)",
                         body[:body.index("\n}")])
        bounds.append(root.group(1))
    return bounds


@pytest.mark.parametrize("counted", [False, True], ids=["all-ids", "count"])
@pytest.mark.parametrize("rows,dim,dtype", [
    (13_882_494, 128, jnp.float32),      # the benchmark's 7.1 GB cold tier
    (1_959_224, 100, jnp.float32),       # products' width: not 128 lanes
    (1_000_000, 128, jnp.int8)])         # a quantized tier's codes
def test_take_rows_out_of_pinned_host_memory(topo, shape_on_chip, rows, dim,
                                             dtype, counted):
    """``placement.take_rows`` over a table in the host's pinned memory is
    a loop of device-initiated DMAs out of host memory space ``S(5)``, no
    host compute, and the chip's compiler takes it at these widths
    (``shape_on_chip`` is asked for because it turns the cache off). With
    a traced ``count`` the loop's bound is a value the program computes;
    over all of ``ids`` its ``while`` compares with a constant."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from quiver_tpu.utils.placement import _ROWS_IN_FLIGHT, take_rows
    # a NamedSharding carries its memory kind into the traced type
    mesh = Mesh(np.array([topo.devices[0]]), ("chip",))
    on_chip = NamedSharding(mesh, P())
    table = jax.ShapeDtypeStruct(
        (rows, dim), dtype,
        sharding=NamedSharding(mesh, P(), memory_kind="pinned_host"))
    args = (table, jax.ShapeDtypeStruct((131_072,), jnp.int32,
                                        sharding=on_chip))
    if counted:
        args += (jax.ShapeDtypeStruct((), jnp.int32, sharding=on_chip),)
    text = jax.jit(take_rows).lower(*args).compile().as_text()
    assert "S(5)" in text and "HostExecute" not in text
    # a turn's fetches, all started before the first is waited for
    assert text.count("dynamic-slice-start(") == _ROWS_IN_FLIGHT
    bounds = _loop_bounds(text)
    assert bounds and counted != any("constant" in b for b in bounds), bounds


def test_the_tiered_step_fetches_its_cold_rows_under_qt_lookup_cold(
        topo, shape_on_chip):
    """A train step over a spliced tiered store, compiled for the described
    chip (a small world: names only; ``shape_on_chip`` is asked for because
    it turns the cache off): the cold tier's row fetches are
    ``dynamic-slice-start`` / ``-done`` pairs out of host memory in scope
    ``qt_lookup_cold`` beneath ``qt_gather``, which is where the
    benchmark's reducers look for them, and no host compute."""
    import re

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import quiver_tpu as qv
    from quiver_tpu import profiling
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel.frontier import feature_splice
    from quiver_tpu.parallel.train import build_train_step
    from quiver_tpu.utils.placement import _ROWS_IN_FLIGHT

    nodes, hot, dim, edges, batch, sizes = 4096, 2048, 128, 20_000, 8, [3, 2]
    # the store is built over real (CPU) tiers of these shapes: the
    # splice closes over its sizes, the step is lowered over shapes
    store = qv.Feature(host_placement="offload", allow_fallback=False,
                       cold_budget=32, dedup_cold=False).from_tiers(
        jnp.zeros((hot, dim)), jax.device_put(
            np.zeros((nodes - hot, dim), np.float32),
            SingleDeviceSharding(jax.devices()[0],
                                 memory_kind="pinned_host")),
        np.arange(nodes, dtype=np.int32))
    _, _, gather = feature_splice(store)
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=len(sizes))
    tx = optax.adam(1e-3)
    step = build_train_step(model, tx, sizes, batch, gather=gather,
                            collect_metrics=True)
    mesh = Mesh(np.array([topo.devices[0]]), ("chip",))
    arr = lambda shape, dtype, kind=None: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(), memory_kind=kind))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: _sage_state([dim, 16, 4], tx)))
    text = step.jitted_fns[-1].lower(
        state, (arr((hot, dim), jnp.float32),
                arr((nodes - hot, dim), jnp.float32, "pinned_host")),
        arr((nodes,), jnp.int32), arr((nodes + 1,), jnp.int32),
        arr((edges,), jnp.int32), arr((batch,), jnp.int32),
        arr((batch,), jnp.int32), arr(key.shape, key.dtype)).compile().as_text()
    assert "HostExecute" not in text
    fetches = [line for line in text.splitlines()
               if re.search(r" dynamic-slice-(start|done)\(", line)]
    # the narrow read's turn; the overflow branch's loop has its own
    assert len(fetches) >= 2 * _ROWS_IN_FLIGHT
    for line in fetches:
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(profiling.QT_GATHER + r"\)?/.*"
                         + profiling.QT_LOOKUP_COLD + r"\)?/", name), name


def test_the_attention_step_fits_beside_a_float16_table(topo, shape_on_chip):
    """The MAG240M cell's first layer at its shapes (``chipbench/configs/
    mag240m-gat-1of32.json``: a frontier of 425,984 rows of 768 float16
    out of a 3.8 M-row table, 26,624 targets x 15 slots, four heads of
    256): the chip's compiler takes a float16 table as it is, and the
    attention's forward and backward pass fit beside it: the per-slot
    rows (``[15, 26624, 1024]`` float32, 1.6 GB a block) are held three
    or four times over at the most."""
    from quiver_tpu.models.gat import gat_attention
    from quiver_tpu.parallel.frontier import masked_feature_gather
    from quiver_tpu.pyg.sage_sampler import Adj
    nodes, width, hidden, heads = 3_804_740, 768, 1024, 4
    targets, k = 26_624, 15
    sources = targets * (k + 1)

    def loss(w, a_src, a_dst, feat, n_id, src, count):
        x = masked_feature_gather(feat, n_id)
        assert x.dtype == jnp.float32
        adj = Adj(jnp.stack([src, src]), None, (sources, targets),
                  fanout=k, valid_targets=count)
        return (gat_attention(x @ w, a_src, a_dst, adj) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape_on_chip((width, hidden), jnp.float32),
        shape_on_chip((heads, hidden // heads), jnp.float32),
        shape_on_chip((heads, hidden // heads), jnp.float32),
        shape_on_chip((nodes, width), jnp.float16),
        shape_on_chip((sources,), jnp.int32),
        shape_on_chip((targets * k,), jnp.int32),
        shape_on_chip((), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    block = 4 * k * targets * hidden
    assert memory.argument_size_in_bytes > 2 * nodes * width   # the table
    # x, H and dH (1.3 + 1.7 + 1.7 GB) and two or three per-slot blocks
    assert memory.temp_size_in_bytes < 6 * block, memory
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        < 15.75e9
