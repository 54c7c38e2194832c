"""Per-tier dtype policy: narrow storage formats with fused dequant.

Feature collection is bandwidth-critical (the paper's second
bottleneck): the currency of every tier — HBM hot set, pinned-host
offload, numpy host, disk mmap — and of the cross-host ``all_to_all``
exchange is BYTES PER ROW. A dtype policy shrinks that currency:

  ``None``/"fp32"  store as-is (identity)
  "bf16"/"fp16"    pure cast — half the bytes, no sidecars; lookups
                   return the narrow float directly (models consume
                   bf16 activations unchanged)
  "int8"           per-row affine quantization — a quarter of the
                   bytes plus an 8-byte/row sidecar (fp32 scale +
                   zero-point); dequantization is FUSED into the
                   gather, so the narrow path reads ``[budget, dim]``
                   int8 + ``[budget, 1]`` sidecars and converts only
                   the gathered rows (FastSample's compression lever,
                   arxiv 2311.17847, composed with the dedup/compaction
                   machinery of ``ops.dedup``).

A quantized tier is a :class:`QuantizedTensor` — a NamedTuple (hence a
pytree) of ``(data[int8, n x d], scale[f32, n x 1], zero[f32, n x 1])``
whose leaves may be numpy (host tier) or jax arrays (HBM / pinned
host / sharded stores). Every helper here accepts either a plain array
or a ``QuantizedTensor`` so tier code stays dtype-agnostic:
``tier_rows`` / ``tier_dim`` / ``tier_dtype`` for shape protocol,
``gather_rows`` for the fused take+dequant, ``take_np`` for the numpy
host path.

``plan_hot_capacity`` is the bandwidth-aware placement planner: narrow
rows shrink ``row_bytes``, so the same HBM budget holds 2-4x more hot
rows — given (byte budget, policy, degree distribution) it returns the
capacity AND the expected degree-mass hit rate next to the width-blind
fp32 sizing, so construction logs the hit-rate gain the policy buys.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.placement import take_rows

POLICIES = (None, "fp32", "fp16", "bf16", "int8")

# per-row sidecar bytes for int8: fp32 scale + fp32 zero-point
_SIDECAR_BYTES = 8


def resolve_policy(policy):
    """Canonicalize a policy name: None/'fp32' -> None (identity)."""
    if policy in (None, "fp32", "float32"):
        return None
    if policy in ("bf16", "bfloat16"):
        return "bf16"
    if policy in ("fp16", "float16"):
        return "fp16"
    if policy == "int8":
        return "int8"
    raise ValueError(
        f"unknown dtype policy {policy!r}; expected one of "
        f"{[p for p in POLICIES if p]} or None")


class QuantizedTensor(NamedTuple):
    """int8 rows + per-row affine sidecars. A pytree: flows through
    jit / shard_map / device_put leaf-wise, so quantized tiers ride the
    same code paths as plain arrays (specs broadcast as prefixes).

    Dequant is ``code * scale + zero`` — ONE fused multiply-add per
    element. The code offset (+128) is folded into ``zero`` at
    quantize time: the three-op form ``(code + 128) * scale + min``
    measures ~25% slower than an fp32 gather on the CPU backend, the
    folded FMA form ~20% faster — the fold is what makes the narrow
    tier a latency win as well as a byte win."""

    data: object    # [n, d] int8 code in [-128, 127]
    scale: object   # [n, 1] f32 — dequant slope
    zero: object    # [n, 1] f32 — row bias (the value of code 0)

    @property
    def shape(self):
        return self.data.shape

    @property
    def nbytes_stored(self) -> int:
        return int(self.data.size + self.scale.size * 4 + self.zero.size * 4)


def is_quantized(t) -> bool:
    return isinstance(t, QuantizedTensor)


def storage_itemsize(policy) -> float:
    """Stored bytes per ELEMENT under ``policy`` (sidecars excluded)."""
    p = resolve_policy(policy)
    return {None: 4, "bf16": 2, "fp16": 2, "int8": 1}[p]


def row_bytes(dim: int, policy=None, base_itemsize: int = 4) -> int:
    """Stored bytes per ROW under ``policy``, sidecars included. The
    bandwidth currency: host-tier traffic and exchange payloads scale
    with this, and the hot-capacity planner divides the byte budget by
    it (width-aware sizing, vs. the width-blind fp32 division)."""
    p = resolve_policy(policy)
    if p is None:
        return dim * base_itemsize
    if p == "int8":
        return dim + _SIDECAR_BYTES
    return dim * 2                      # bf16 / fp16


def quantize(x, policy, axis: int = 1):
    """Encode ``x`` under ``policy``. Plain-cast policies return a cast
    ARRAY (bf16/fp16 rows are consumed directly); "int8" returns a
    :class:`QuantizedTensor` with per-row fp32 scale/zero sidecars.
    numpy in -> numpy out (host tiers stay host arrays); jax in -> jax.
    """
    p = resolve_policy(policy)
    if p is None:
        return x
    if p in ("bf16", "fp16"):
        dt = jnp.bfloat16 if p == "bf16" else jnp.float16
        return x.astype(dt)
    xp = np if isinstance(x, np.ndarray) else jnp
    xf = x.astype(np.float32 if xp is np else jnp.float32)
    mn = xf.min(axis=axis, keepdims=True)
    mx = xf.max(axis=axis, keepdims=True)
    scale = (mx - mn) / 255.0
    # constant rows (mn == mx) get slope 1 so dequant returns mn exactly
    scale = xp.where(scale <= 0, xp.ones_like(scale), scale)
    code = xp.clip(xp.rint((xf - mn) / scale) - 128, -128, 127)
    # fold the +128 code offset into the bias: dequant is then ONE
    # multiply-add per element (see QuantizedTensor)
    zero = mn + 128.0 * scale
    # sidecars carry the store's LOGICAL dtype: a bf16 store quantized
    # to int8 must dequantize back to bf16 (tier_dtype = scale.dtype),
    # not silently upcast every lookup to fp32 — the math above still
    # runs in fp32 for rounding accuracy
    side_dt = (x.dtype if jnp.issubdtype(jnp.dtype(x.dtype), jnp.floating)
               else xf.dtype)
    return QuantizedTensor(code.astype(np.int8 if xp is np else jnp.int8),
                           scale.astype(side_dt), zero.astype(side_dt))


def dequantize(t, dtype=None):
    """Decode rows. Plain arrays pass through (optionally cast)."""
    if not is_quantized(t):
        return t if dtype is None else t.astype(dtype)
    # scale.dtype IS the store's logical dtype (see quantize): decode
    # in it so dequantize and gather_rows agree bit-for-bit
    out = t.data.astype(t.scale.dtype) * t.scale + t.zero
    return out if dtype is None else out.astype(dtype)


def tier_rows(t) -> int:
    return t.data.shape[0] if is_quantized(t) else t.shape[0]


def tier_dim(t) -> int:
    return t.data.shape[1] if is_quantized(t) else t.shape[1]


def tier_dtype(t):
    """The dtype LOOKUPS of this tier produce (dequantized width)."""
    if is_quantized(t):
        return jnp.dtype(t.scale.dtype)
    return jnp.dtype(t.dtype)


def tier_parts(t):
    """Split a tier into its storage leaves for kernel plumbing:
    ``(codes, scale, zero)`` for a quantized tier, ``(t, None, None)``
    for a plain array. The fused Pallas hop passes these as separate
    pallas_call operands (a NamedTuple cannot cross the kernel ABI) and
    applies the same folded ``code * scale + zero`` FMA in-register, so
    the kernel and :func:`gather_rows` stay bit-identical."""
    if is_quantized(t):
        return t.data, t.scale, t.zero
    return t, None, None


def row_read_bytes(t) -> int:
    """Bytes one row LOOKUP of this tier moves from storage (codes +
    sidecars for int8, the row itself otherwise) — the per-row DMA cost
    the fused kernel's CostEstimate and the bench byte models charge."""
    if is_quantized(t):
        return int(tier_dim(t) + t.scale.dtype.itemsize
                   + t.zero.dtype.itemsize)
    return int(tier_dim(t) * jnp.dtype(t.dtype).itemsize)


def tier_key(t):
    """Hashable identity of a tier's stored layout (executable-cache
    keys: shape + every leaf dtype, so an fp32 and an int8 store of the
    same logical shape never share a compiled program)."""
    if is_quantized(t):
        return ("q8", tuple(t.data.shape), str(t.scale.dtype))
    return (tuple(t.shape), str(t.dtype))


def gather_rows(t, ids, count=None):
    """``jnp.take(t, ids, axis=0)`` with dequantization FUSED: a
    quantized tier reads ``[k, d]`` int8 + two ``[k, 1]`` sidecars and
    converts only the gathered rows — the whole-table width never moves.
    ``ids`` must already be clipped in-range (callers own masking).
    A tier in pinned host memory has its narrow rows fetched by the
    device before the decode (``placement.take_rows``, which says what
    ``count`` is: how many of ``ids`` a host tier's loops fetch; rows at
    or past it must not be read)."""
    if not is_quantized(t):
        return take_rows(t, ids, count)
    code = take_rows(t.data, ids, count)
    scale = take_rows(t.scale, ids, count)
    zero = take_rows(t.zero, ids, count)
    return code.astype(scale.dtype) * scale + zero


def take_np(t, ids):
    """The numpy host path's fancy-index + dequant (host rows stay
    numpy until the scatter onto the device result)."""
    if not is_quantized(t):
        return t[ids]
    # decode through float64 then round once to the logical dtype:
    # numerically this IS the fused multiply-add (the f64 product of
    # two f32/bf16 values is exact and the double rounding is
    # innocuous at >= 2p+2 spare bits), so the numpy path rounds
    # identically to the jitted XLA decode and the Pallas kernel's
    # in-register FMA — which is what lets an online hot-set rotation
    # move a row between decode engines bit-identically
    out = (t.data[ids].astype(np.float64)
           * np.asarray(t.scale[ids], np.float64)
           + np.asarray(t.zero[ids], np.float64))
    return out.astype(t.scale.dtype)


def tree_map_tier(fn, t):
    """Apply ``fn`` to the tier's storage leaves (placement, padding,
    pickling round-trips) preserving the QuantizedTensor wrapper."""
    if is_quantized(t):
        return QuantizedTensor(fn(t.data), fn(t.scale), fn(t.zero))
    return fn(t)


def default_cold_budget(n: int) -> int:
    """The tiered lookup's default per-batch host-row budget (shared by
    ``Feature.lookup_tiered``, ``dedup_feature_gather``, and the bench
    byte models so the constant can't drift between them)."""
    return max(n // 4, 256)


def dedup_rows_read(ids, budget: int | None = None,
                    cold_count: int | None = None) -> int:
    """Analytic mirror of the fused dedup tiered lookup's host-row
    count for one batch (``lookup_tiered``'s branch structure), as an
    UPPER bound: ``budget`` rows on the narrow path; on unique-overflow
    the lookup falls back to the COLD-COMPACTION path, which reads at
    most ``budget`` rows (over a pinned-host tier it fetches the cold
    count, rounded up to a turn of ``placement.take_rows``' loop, and
    not the budget) unless the batch's raw cold-slot count
    (``cold_count``; translated ids >= cache_rows) overflows too — only
    then does the full batch move. ``cold_count=None`` assumes every
    slot may be cold (the conservative upper bound). The benches'
    bytes/batch figures both come from this ONE copy of the branch
    logic; the structural (jaxpr-level) pin of the same bounds lives in
    tests/_traffic.py."""
    ids = np.asarray(jax.device_get(ids))
    n = int(ids.shape[0])
    if budget is None:
        budget = default_cold_budget(n)
    if budget >= n:
        return n
    uniq = np.unique(ids[ids >= 0]).size
    if uniq <= budget:
        return budget
    if cold_count is None:
        cold_count = n
    return budget if cold_count <= budget else n


class HotPlan(NamedTuple):
    """Bandwidth-aware hot-tier sizing under a dtype policy."""

    rows: int                    # hot rows the budget holds under policy
    row_bytes: int               # stored bytes/row (sidecars included)
    expected_hit_rate: Optional[float]   # degree-mass share, if degrees
    fp32_rows: int               # the width-blind sizing, for the log
    fp32_hit_rate: Optional[float]


def plan_hot_capacity(budget_bytes: int, total_rows: int, dim: int,
                      policy=None, base_itemsize: int = 4,
                      degree=None) -> HotPlan:
    """Pick hot-tier capacity from (byte budget, dtype policy, degree
    distribution). Narrow rows shrink ``row_bytes``, so the same budget
    holds 2-4x more hot rows; under degree-proportional access (what
    GNN minibatch gathers look like) the expected HBM hit rate is the
    cached rows' share of total degree mass — returned next to the
    width-blind fp32 sizing so callers can log the gain."""
    rb = row_bytes(dim, policy, base_itemsize)
    rows = min(total_rows, budget_bytes // max(rb, 1))
    rb32 = dim * base_itemsize
    rows32 = min(total_rows, budget_bytes // max(rb32, 1))
    hit = hit32 = None
    if degree is not None and total_rows:
        deg = np.sort(np.asarray(jax.device_get(degree),
                                 np.float64))[::-1]
        mass = np.concatenate([[0.0], np.cumsum(deg)])
        total = mass[-1] or 1.0
        hit = float(mass[min(rows, deg.size)] / total)
        hit32 = float(mass[min(rows32, deg.size)] / total)
    return HotPlan(int(rows), int(rb), hit, int(rows32), hit32)
