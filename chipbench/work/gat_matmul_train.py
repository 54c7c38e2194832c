"""``work/gat_matmul.py``'s FLOPs with the backward pass, for a reducer
that passes no argument (``scope_roofline`` over ``qt_project``, whose
ops are the products of the forward AND of the backward pass)."""

from chipbench import spec


def work(cell) -> dict:
    return spec.plugin("work", "gat_matmul").work(cell, train=True)
