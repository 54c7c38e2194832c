"""FLOPs of the relational attention model's matrix products for one
batch of the cell: what ``step.mfu.*`` counts for a configuration that
names ``"step_flops": "rgat_matmul"``.

Every product with a weight matrix of ``MAG240MGNN(model="gat",
relations=R)``, as the mathematics needs it: per layer every edge slot's
source row by its own relation's weight, ``[targets x fanout, in] x [in,
hidden]`` (a row under one relation, not under all R), and by its
relation's ``W_r a_src_r``, ``[targets x fanout, in] x [in, heads]``, the
targets' logit terms ``[targets, in] x [in, R x heads]`` (``x_i . (W_r
a_dst_r)``), and
the skip of the targets ``[targets, in] x [in, hidden]``; then the head
``[batch, hidden] x [hidden, hidden]`` and ``[batch, hidden] x [hidden,
classes]``, ``2 m k n`` each, at the static caps. The backward pass costs
twice the forward, but the first layer's input is data and wants no
gradient, so its products cost once. The attention's logits, softmax and
weighted sum are left out, as MFU conventionally does. MAG240M, batch
1024, fanout [25, 15], 5 relations: forward 732e9, with the backward
pass 1,522e9."""

from chipbench import flops


def step_flops(cell, train: bool) -> float:
    cfg = cell.config
    caps = [cell.batch] + flops.frontier_caps(cell.batch, cfg["fanout"])
    hidden, heads = cfg["hidden_dim"], cfg["heads"]
    relations = cfg["num_relations"]
    total = 0.0
    for i in range(cfg["num_layers"]):
        targets = caps[-2 - i]
        slots = targets * cfg["fanout"][-1 - i]
        fan_in = cfg["feature_dim"] if i == 0 else hidden
        fwd = 2.0 * fan_in * (slots * (hidden + heads)
                              + targets * relations * heads
                              + targets * hidden)
        total += fwd * ((2.0 if i == 0 else 3.0) if train else 1.0)
    head = 2.0 * cell.batch * hidden * (hidden + cfg["num_classes"])
    return total + head * (3.0 if train else 1.0)


def work(cell, train: bool = False) -> dict:
    return {"flops": step_flops(cell, bool(train))}
