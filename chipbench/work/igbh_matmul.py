"""FLOPs of the MLPerf R-GAT's matrix products for one batch of the cell:
what ``step.mfu.*`` counts for a configuration that names
``"step_flops": "igbh_matmul"``.

Every product with a weight matrix of ``quiver_tpu.models.RGAT``, as the
mathematics needs it, at the configuration's static frontier caps
(``frontier_caps``: the rows each node type's frontier keeps after each
hop): per layer, for every relation ``r = (t, s)`` drawn at its hop, the
source type's rows by ``W_src_r``, ``[rows of s after the hop, in] x [in,
hidden]``, and the target type's rows by ``W_dst_r a_dst_r``, ``[rows of
t before the hop, in] x [in, heads]``; then the head ``[batch, hidden] x
[hidden, classes]``; ``2 m k n`` each. The backward pass costs twice the
forward, but the first layer's input is data and wants no gradient, so
its products cost once more, not twice. The attention's logits, softmax
and weighted sum are left out, as MFU conventionally does. A relation is
drawn at a hop where its target type has a frontier (the batch's type at
hop 0, and every type a drawn relation reaches after)."""

def _frontiers(cfg, batch):
    """``[hop][type]`` rows of each type's frontier before each hop, the
    batch at hop 0 (0: none), and after the last."""
    names = list(cfg["node_types"])
    pairs = [(names.index(r["target"]), names.index(r["source"]))
             for r in cfg["relations"]]
    rows = [[batch if t == 0 else 0 for t in range(len(names))]]
    for hop in range(len(cfg["fanout"])):
        reach = {s for t, s in pairs if rows[-1][t]} | {
            t for t in range(len(names)) if rows[-1][t]}
        rows.append([cfg["frontier_caps"][n][hop] if t in reach else 0
                     for t, n in enumerate(names)])
    return rows, pairs


def step_flops(cell, train: bool) -> float:
    cfg = cell.config
    rows, pairs = _frontiers(cfg, cell.batch)
    hidden, heads = cfg["hidden_dim"], cfg["heads"]
    hops = len(cfg["fanout"])
    total = 0.0
    for i in range(cfg["num_layers"]):
        hop = hops - 1 - i
        before, after = rows[hop], rows[hop + 1]
        fan_in = cfg["feature_dim"] if i == 0 else hidden
        fwd = sum(2.0 * fan_in * (after[s] * hidden + before[t] * heads)
                  for t, s in pairs if before[t])
        total += fwd * ((2.0 if i == 0 else 3.0) if train else 1.0)
    head = 2.0 * cell.batch * hidden * cfg["num_classes"]
    return total + head * (3.0 if train else 1.0)


def work(cell, train: bool = False) -> dict:
    return {"flops": step_flops(cell, bool(train))}
