"""Bytes of the row exchange for one batch of one chip of the cell
(``flops.gather_bytes`` at the frontier's static cap): every slot of the
last hop's frontier has its row read once from the table, wherever it
lies, and written once into the chip's frontier block, and its 4-byte id
read. What carries the rows between the chips (collectives, rounds, a
kernel) is the program's business: the least the exchange can move is
what a one-chip gather moves, so the exchange's share of its roofline
reads beside the one-chip gather's."""

from chipbench import flops


def work(cell) -> dict:
    rows = flops.frontier_caps(cell.batch, cell.config["fanout"])[-1]
    return {"bytes": flops.gather_bytes(rows, cell.config["feature_dim"])}
