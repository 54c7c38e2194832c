"""Bytes of one step's exchange, from the cell's shapes: every row of the
batch is read where it lives and written where it is used."""


def work(cell) -> dict:
    return {"bytes": 2.0 * cell.batch * cell.config["feature_dim"] * 4}
