"""The entry ``micro_batch_server``: ``MicroBatchServer.submit`` over a
``ServeEngine`` with one fanout variant; the traffic's ``kind`` chooses
the open or the closed loop."""

from chipbench.serve_cell import ServeRun as Run  # noqa: F401
