"""Bytes of the attention for one training batch of the cell.

Per layer the least the attention can move: every edge slot's projected
row (``hidden`` floats of 4 bytes; a target's self edge is a slot too,
so ``targets x (fanout + 1)`` rows) read once, and every target's row
written once. The forward pass does that once, the backward pass twice
(it reads the rows again to form the weights' and the rows' cotangents,
and writes a cotangent for every slot's row): three times over.
MAG240M, batch 1024, fanout [25, 15]: 5.9e9 bytes a step. The logits and
weights (``heads`` floats a slot) are left out; the bound is HBM
bandwidth."""

from chipbench import flops


def work(cell) -> dict:
    cfg = cell.config
    caps = [cell.batch] + flops.frontier_caps(cell.batch, cfg["fanout"])
    row = 4.0 * cfg["hidden_dim"]
    total = 0.0
    for targets, k in zip(caps[:-1], cfg["fanout"]):
        total += targets * (k + 1) * row + targets * row
    return {"bytes": 3.0 * total}
