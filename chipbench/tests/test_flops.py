"""The FLOP and byte functions against hand counts at the products shapes."""

from chipbench import flops


def test_frontier_caps_products():
    assert flops.frontier_caps(1024, [15, 10, 5]) == [16384, 180224, 1081344]


def test_forward_flops_products():
    # layer 0: 180,224 targets x (100 -> 256), root and neighbour matmuls
    l0 = 2 * 2 * 180224 * 100 * 256
    l1 = 2 * 2 * 16384 * 256 * 256
    l2 = 2 * 2 * 1024 * 256 * 47
    got = flops.sage_matmul_flops(1024, [15, 10, 5], [100, 256, 256, 47], False)
    assert got == l0 + l1 + l2
    assert abs(got - 22.8e9) < 0.05e9


def test_train_flops_products():
    fwd = flops.sage_matmul_flops(1024, [15, 10, 5], [100, 256, 256, 47], False)
    l0 = 2 * 2 * 180224 * 100 * 256
    # backward = 2 x forward, less the first layer's input gradient
    got = flops.sage_matmul_flops(1024, [15, 10, 5], [100, 256, 256, 47], True)
    assert got == fwd + 2 * fwd - l0


def test_gather_bytes_products():
    # 1,081,344 rows of 400 B read and written, a 4-byte id each
    assert flops.gather_bytes(1081344, 100) == 1081344 * (400 + 400 + 4)
