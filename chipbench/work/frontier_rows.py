"""Bytes of the frontier gather for one batch of the cell at the
configuration's STORAGE width (``flops.gather_bytes`` with the itemsize of
``precision.storage``): every slot of the last hop's frontier reads one
row of the table as it is stored and writes it, and reads its 4-byte id.
``work/frontier_gather.py`` counts 4-byte rows."""

from chipbench import flops

ITEMSIZE = {"float32": 4, "float16": 2, "bfloat16": 2}


def work(cell) -> dict:
    cfg = cell.config
    rows = flops.frontier_caps(cell.batch, cfg["fanout"])[-1]
    return {"bytes": flops.gather_bytes(
        rows, cfg["feature_dim"], ITEMSIZE[cfg["precision"]["storage"]])}
