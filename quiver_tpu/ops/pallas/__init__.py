"""Pallas TPU kernels.

Status after PR 23: every kernel here compiles for the v5e at
ogbn-products sizes (``tests/test_chip_compile.py`` asks the chip's
compiler on every test run) and ``chip_smoke.py`` runs the fused train
step on the chip. None has a timing yet; the production default for both
hot ops stays the jnp/XLA path (``ops/sample.py``, ``jnp.take``).

- ``sample_kernel.py`` (``sample_layer_pallas``) and ``gather.py``
  (``gather_rows``) mirror the reference's warp-per-seed sampler and
  warp-per-row gather. They are on no production call path;
  ``sample_layer_pallas`` is the split oracle the fused kernels are
  pinned against.
- ``fused.py`` fuses the hop walk and the hot-tier feature gather into
  ONE kernel, so the frontier id list never round-trips through HBM
  between a sample program and a gather program — something no jnp
  graph can express (XLA materializes the ids between the two gathers).
  It IS reachable from production builders, strictly opt-in:
  ``build_train_step(fused_hot_hop=True)`` /
  ``build_serve_step(fused_hot_hop=True)`` / ``ServeEngine`` /
  ``build_e2e_train_step`` and the hot-tier leg of
  ``build_sharded_serve_step``, exact method only. ``fused_multihop``
  walks ANY fanout ladder: interior hops run the sampling-only kernel
  variant (degrees/starts resolve in-kernel, no XLA indptr gather), the
  sort-based gather-free ``compact_layer`` dedups between hops, and
  only the LEAF hop's feature rows are ever written to HBM, so the
  modeled ``gather_index_bytes`` is zero across every hop. The whole
  walk — kernels, compaction, the final two-scatter row reassembly —
  compiles as one program. The split oracles are
  ``fused_hot_hop_reference`` / ``fused_multihop_reference``,
  bit-equality pinned in ``tests/test_fused.py``. Per-hop frontier
  budgets truncate exactly as the split path's ``compact_layer``
  budgets do — duplicates compact first, overflow drops from the tail —
  so fused and split walks always agree bit-for-bit, truncation
  included.

Nothing here falls back: ``interpret`` / ``rng`` default from the
backend in ONE place (``_dma.default_interpret`` / ``default_rng``:
compiled with the on-core generator on a TPU, interpreted with the
portable hash generator elsewhere), and what the chip's compiler
refuses raises (the fused gather over an int8 table). The layout rules
the compiler imposes live in ``_dma.py``.
"""

__all__ = []
