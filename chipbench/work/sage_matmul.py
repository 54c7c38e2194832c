"""FLOPs of the SAGE layers' matrix products for one batch of the cell
(``flops.sage_matmul_flops``): what ``step.mfu.*`` counts for a
configuration that names no ``step_flops``."""

from chipbench import flops


def work(cell, train: bool = False) -> dict:
    return {"flops": flops.sage_matmul_flops(
        cell.batch, cell.config["fanout"], cell.dims, bool(train))}
