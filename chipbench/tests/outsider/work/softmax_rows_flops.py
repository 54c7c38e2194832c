"""FLOPs of the two-line model for one batch: the one matrix product,
forward (and twice that backward when training)."""


def work(cell, train: bool = False) -> dict:
    fwd = 2.0 * cell.batch * cell.config["feature_dim"] \
        * cell.config["num_classes"]
    return {"flops": fwd * (3 if train else 1)}
