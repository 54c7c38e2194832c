"""The entry ``dp_train_step``: ``build_e2e_train_step`` data-parallel over
a ``("data",)`` mesh of the cell's chips, the world replicated on each, one
batch a chip and one gradient all-reduce a step."""

from chipbench.train_cell import TrainRun


class Run(TrainRun):
    def build_step(self, model, tx, mesh, **extra):
        from quiver_tpu.parallel.train import build_e2e_train_step
        return build_e2e_train_step(model, tx, self.fanout, self.batch, mesh,
                                    **extra)
