"""Bytes of the cold read for one batch of the cell: the configuration's
``cold_budget`` rows, each read once out of the host tier and written once
into the frontier block, and its 4-byte id read (``flops.gather_bytes``).
The budget, not the miss count, is what the lookup reads every step, so
whatever implements the cold read is judged against the same bytes."""

from chipbench import flops


def work(cell) -> dict:
    return {"bytes": flops.gather_bytes(int(cell.config["cold_budget"]),
                                        cell.config["feature_dim"])}
