"""The on-chip benchmark of quiver_tpu (BENCHMARK.json at the repo root).

Everything that decides a number lives here: the world and the traffic
made from ``--seed``, the plain float32 reference, the reduction from
traces and counters to metrics, the FLOP and byte functions and the
table of peaks. From ``quiver_tpu`` it takes only the entries the cells
drive. See README.md.
"""
