"""FLOPs of the attention model's matrix products for one batch of the
cell: what ``step.mfu.*`` counts for a configuration that names
``"step_flops": "gat_matmul"``.

Every product with a weight matrix of ``MAG240MGNN(model="gat")``: per
layer the shared projection of the layer's sources ``[S, in] x [in,
hidden]`` and the skip of its targets ``[T, in] x [in, hidden]``, then
the head ``[batch, hidden] x [hidden, hidden]`` and ``[batch, hidden] x
[hidden, classes]``, ``2 m k n`` each, at the frontier's static caps. The
backward pass costs twice the forward, but the first layer's input is
data and wants no gradient, so its two products cost once. The
attention's logits, softmax and weighted sum are left out, as MFU
conventionally does. MAG240M, batch 1024, fanout [25, 15]: forward 772e9,
with the backward pass 1,605e9."""

from chipbench import flops


def step_flops(cell, train: bool) -> float:
    cfg = cell.config
    caps = [cell.batch] + flops.frontier_caps(cell.batch, cfg["fanout"])
    hidden = cfg["hidden_dim"]
    total = 0.0
    for i in range(cfg["num_layers"]):
        sources, targets = caps[-1 - i], caps[-2 - i]
        fan_in = cfg["feature_dim"] if i == 0 else hidden
        fwd = 2.0 * (sources + targets) * fan_in * hidden
        total += fwd * ((2.0 if i == 0 else 3.0) if train else 1.0)
    head = 2.0 * cell.batch * hidden * (hidden + cfg["num_classes"])
    return total + head * (3.0 if train else 1.0)


def work(cell, train: bool = False) -> dict:
    return {"flops": step_flops(cell, bool(train))}
