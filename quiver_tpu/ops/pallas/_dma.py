"""Shared Mosaic DMA/window math + pluggable kernel PRNG.

One home for the layout rules every HBM-streaming kernel in this
package must agree on. They are what the v5e's compiler accepts
(learned by compiling for a described ``v5e:2x2`` device, PR 23):

- a 1-D int32 array in HBM is tiled by 1024, and Mosaic refuses any DMA
  slice of it that is not a multiple of 1024 long. A 2-D ``[rows, 128]``
  array can be sliced at ANY row with ANY row count. So both CSR arrays
  reach the kernels as 128-lane rows: ``pad_indices`` / ``as_rows``.
- ``win``/``win_rows``/``split_start``: a neighbor read covers the
  ``win_rows(row_cap)`` rows from the one holding the row's first entry;
  the <=127-entry residual shifts the position compare, not the DMA.
- a DMA destination inside a 2-D VMEM scratch must be a whole (8, 128)
  tile, so staging buffers are 3-D ``[slot, rows, 128]`` and a DMA fills
  ``buf.at[slot]``.
- ``pad_feature_dim``: per-row feature DMAs need the row width to be a
  multiple of 128 lanes; tables that are not get zero-padded with a
  trace-time warning (a full-table HBM copy per call — hot paths should
  store tables pre-padded).

``make_rand_bits`` is the kernels' PRNG provider. Two interchangeable
backends drawing identical *roles* (a uint32 ``[bs, 1]`` column per call):

  "tpu"   the on-core generator (``pltpu.prng_seed`` +
          ``prng_random_bits``) — the production TPU path. This jax
          has no CPU interpret lowering for those primitives, so
          kernels built with it are TPU-only.
  "hash"  a pure-jnp counter-based Wang/Murmur-style integer mix —
          interprets everywhere AND compiles on TPU. Deterministic in
          (seed, block, call index), so two kernels seeded alike draw
          identical streams: this is what makes the fused kernel's
          bit-equivalence tests vs the two-program oracle runnable on
          CPU.

Both backends are seeded per grid block (``seed + block`` for "tpu", a
block-salted hash for "hash") so blocks draw independent streams.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# lane width: CSR arrays reach the kernels as [rows, ALIGN] so a DMA can
# start at any row (see the module docstring)
ALIGN = 128

RNGS = ("tpu", "hash")


def win_rows(row_cap: int) -> int:
    """Rows of ``ALIGN`` entries one neighbor read stages: ``row_cap``
    entries plus one row, because the first entry can sit up to ALIGN-1
    entries into its row."""
    return -(-row_cap // ALIGN) + 1


def win(row_cap: int) -> int:
    """Staging-window width in entries for a ``row_cap`` neighbor read."""
    return win_rows(row_cap) * ALIGN


def as_rows(x: jax.Array, extra: int = 0) -> jax.Array:
    """A 1-D array as ``[rows, ALIGN]``, zero-padded by at least
    ``extra`` entries and up to a whole row."""
    total = x.shape[0] + extra
    pad = extra + (-total) % ALIGN
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x.reshape(-1, ALIGN)


def pad_indices(indices: jax.Array, row_cap: int) -> jax.Array:
    """CSR ``indices`` in the layout the kernels read: ``[rows, ALIGN]``
    with ``win(row_cap)`` trailing sentinel entries, so a window DMA that
    starts in the last real row can overread safely."""
    return as_rows(indices, win(row_cap))


def split_start(start):
    """Split an element offset into (row, residual < ALIGN). Works on
    traced scalars and vectors alike; the residual shifts the in-window
    position compare."""
    return start // ALIGN, start % ALIGN


def pad_feature_dim(feat: jax.Array, op: str = "gather"):
    """Zero-pad a feature table's row width up to the next multiple of
    128 lanes (per-row HBM DMA requirement). Emits a trace-time warning
    when it fires: the pad is a full-table HBM copy PER CALL — a
    hot-path cliff callers should avoid by storing tables pre-padded."""
    out_dim = feat.shape[1]
    if out_dim % 128:
        import warnings
        warnings.warn(
            f"{op}: feature dim {out_dim} is not a multiple of 128 — "
            "padding the whole table on every call (full-table HBM "
            "copy). Store the table pre-padded to avoid this.",
            stacklevel=3)
        feat = jnp.pad(feat, ((0, 0), (0, 128 - out_dim % 128)))
    return feat


def _mix_u32(x):
    """Wang-style 32-bit integer finalizer (full avalanche)."""
    x = (x ^ jnp.uint32(61)) ^ (x >> 16)
    x = x * jnp.uint32(9)
    x = x ^ (x >> 4)
    x = x * jnp.uint32(0x27D4EB2D)
    x = x ^ (x >> 15)
    return x


def make_rand_bits(rng: str, seed, blk):
    """Return ``rand_bits(bs) -> uint32[bs, 1]``, the kernels' draw op:
    one value per seed, seeds along sublanes like every per-seed vector
    in the kernels (Mosaic's layout pass aborts on rank-1 vector math).

    ``seed`` is a traced int32 scalar, ``blk`` the grid block id. The
    returned callable must be invoked the same number of times in the
    same order by any two kernels that are meant to draw identical
    streams (the call index is part of the "hash" backend's counter).
    """
    if rng == "tpu":
        pltpu.prng_seed(seed + blk)

        def rand_bits(bs: int):
            return pltpu.bitcast(
                pltpu.prng_random_bits((bs, 1)), jnp.uint32)

        return rand_bits
    if rng == "hash":
        base = _mix_u32(
            seed.astype(jnp.uint32)
            ^ (jnp.uint32(0x9E3779B9) * (blk.astype(jnp.uint32) + 1)))
        state = {"step": 0}

        def rand_bits(bs: int):
            step = state["step"]
            state["step"] += 1
            lane = jax.lax.broadcasted_iota(jnp.uint32, (bs, 1), 0)
            x = (base ^ (lane * jnp.uint32(0x85EBCA6B))
                 ^ jnp.uint32((step * 0x9E3779B9) & 0xFFFFFFFF))
            return _mix_u32(_mix_u32(x))

        return rand_bits
    raise ValueError(f"unknown kernel rng {rng!r}; expected one of {RNGS}")


def default_rng() -> str:
    """"tpu" on TPU backends (on-core generator), "hash" elsewhere
    (this jax cannot interpret the pltpu prng primitives on CPU)."""
    return "tpu" if jax.default_backend() == "tpu" else "hash"


def default_interpret() -> bool:
    """Interpret mode everywhere but on a real TPU backend."""
    return jax.default_backend() != "tpu"
