"""Bytes of the frontier gather for one batch of the cell
(``flops.gather_bytes``): every slot of the last hop's frontier reads one
row of the table and writes it, and reads its 4-byte id."""

from chipbench import flops


def work(cell) -> dict:
    rows = flops.frontier_caps(cell.batch, cell.config["fanout"])[-1]
    return {"bytes": flops.gather_bytes(rows, cell.config["feature_dim"])}
