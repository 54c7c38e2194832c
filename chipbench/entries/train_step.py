"""The entry ``train_step``: ``quiver_tpu.parallel.train.build_train_step``
on one chip, the exact sampler."""

from chipbench.train_cell import TrainRun


class Run(TrainRun):
    def build_step(self, model, tx, mesh, **extra):
        from quiver_tpu.parallel.train import build_train_step
        return build_train_step(model, tx, self.fanout, self.batch,
                                method="exact", **extra)
