"""Operations and bytes the algorithm needs, from the cell's shapes alone.

Nothing here looks at the program: a frontier's capacity follows from the
batch and the fanout, a layer's matmuls from the widths.
"""

from __future__ import annotations

from typing import Sequence


def frontier_caps(batch: int, fanout: Sequence[int]) -> list:
    """Slots after each hop: every slot of a hop draws ``k`` neighbours.
    ``frontier_caps(1024, [15, 10, 5]) == [16384, 180224, 1081344]``."""
    caps, cur = [], int(batch)
    for k in fanout:
        cur = cur + cur * int(k)
        caps.append(cur)
    return caps


def sage_matmul_flops(batch: int, fanout: Sequence[int], dims: Sequence[int],
                      train: bool) -> float:
    """FLOPs of the SAGE layers' matrix products for one batch.

    Layer ``i`` (outermost hop first) multiplies its ``targets`` rows by
    a root and a neighbour matrix, ``2 * 2 * targets * in * out``. The
    backward pass costs twice the forward, but the first layer's input
    is data and wants no gradient, so it costs once. Aggregation's adds
    and the softmax are left out, as MFU conventionally does.
    Products, batch 1024: forward 22.8e9."""
    caps = frontier_caps(batch, fanout)
    targets = ([batch] + caps[:-1])[::-1]
    total = 0.0
    for i, t in enumerate(targets):
        fwd = 2.0 * 2.0 * t * dims[i] * dims[i + 1]
        total += fwd
        if train:
            total += fwd if i == 0 else 2.0 * fwd
    return total


def gather_bytes(rows: int, dim: int, itemsize: int = 4) -> float:
    """The frontier gather: every row read once and written once, and its
    4-byte id read. Products, 1,081,344 rows of 400 B."""
    return float(rows) * (2 * dim * itemsize + 4)
