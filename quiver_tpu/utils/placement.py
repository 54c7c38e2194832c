"""Host-offload (pinned host memory) placement with loud fallback.

Shared by the sampler's HOST mode and the Feature store's offload host
tier. A silently different performance regime is the failure mode the
reference guards with its CUDA check macros (quiver.cu.hpp:16-26), so
backends without usable host-offload either warn via the package
logger (allow_fallback=True) or raise.

jax types the memory space of every value: one op takes operands of ONE
space, so a device-space index vector cannot gather from a host-space
table ("memory_space of all inputs passed to `gather` must be the
same"). ``take_rows`` is the read that respects that (the device fetches
the rows itself, one slice of the host buffer a row), and what the
usability probe runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..debug import log as _log


# rows of a pinned-host table a turn of the loop fetches (all in flight
# at once, their ids read and their block written together)
_ROWS_IN_FLIGHT = 32
_LANES, _SUBLANES = 128, 8


def take_rows(table, ids, count=None):
    """``jnp.take(table, ids, axis=0)`` for a table in either memory
    space; ``ids`` must be in range. Rows of a pinned-host table are
    fetched BY THE DEVICE, one DMA a row (a ``dynamic_slice`` of the host
    buffer, the form XLA's host offloader moves itself), in a loop
    (``_fetch_rows``): time and host traffic go with the rows fetched.
    ``count`` (a traced int32 scalar, ``0 <= count <= ids.shape[0]``)
    says how many of ``ids`` matter: the loop then stops after the turn
    that holds row ``count - 1``, rows past that turn are zeros, and the
    caller must not read rows at or past ``count``. Without it every id
    is fetched; a table in device memory is one ``jnp.take`` whatever
    ``count`` is.
    The same gather run as host compute (``compute_on("device_host")``,
    what this was until PR 32) converts the WHOLE table for the host's
    program on every call: 2.3-2.7 s and ~2.9 GB of host memory a call
    out of a 7.1 GB tier, against 0.065 s here, and a step loop dies of
    it (PERF.md section 6, PR 32); left to the device as one ``gather`` it
    aborts the offloader. The chip's compiler refuses a one-row piece of
    a table whose rows are not a whole number of 128-lane vectors, so
    such a table gives each DMA the aligned group of 8 rows that holds
    the row asked for, and the row is picked out of it on the device."""
    if jax.typeof(table).memory_space != jax.memory.Space.Host:
        return jnp.take(table, ids, axis=0)
    return _fetch_rows(table, ids, count)


def _fetch_rows(table, ids, count=None):
    """The loop of ``take_rows`` (which says what ``count`` is), over a
    table of any memory space. A turn handles ``_ROWS_IN_FLIGHT`` rows:
    ONE read of their ids, a fetch each, ONE write of their block (for a
    128-lane table whole ``[8, 128]`` tiles). With ``count`` it runs
    ``ceil(count / _ROWS_IN_FLIGHT)`` turns, else all of them; ids and
    block are padded to a whole number of turns. No index is wrapped
    (``allow_negative_indices=False``: every one is in range by
    contract, and the three scalar ops of the wrap cost a row more than
    its fetch does; PERF.md section 6, PR 33)."""
    n, tail = table.shape[0], table.shape[1:]
    zeros = (0,) * len(tail)
    k, per = ids.shape[0], _ROWS_IN_FLIGHT
    turns = -(-k // per)
    ids = ids.astype(jnp.int32)
    group = 1 if tail and tail[-1] % _LANES == 0 else min(_SUBLANES, n)
    first = jnp.minimum(ids // group * group, n - group)
    padded = jnp.pad(first, (0, turns * per - k))

    def turn(t, out):
        at = jax.lax.dynamic_slice(padded, (t * per,), (per,),
                                   allow_negative_indices=False)
        pieces = [jax.device_put(jax.lax.dynamic_slice(
            table, (at[j],) + zeros, (group,) + tail,
            allow_negative_indices=False), jax.memory.Space.Device)
            for j in range(per)]
        return jax.lax.dynamic_update_slice(
            out, jnp.concatenate(pieces), (t * per * group,) + zeros,
            allow_negative_indices=False)

    rows = jax.lax.fori_loop(
        0, turns if count is None else (count + per - 1) // per, turn,
        jnp.zeros((turns * per * group,) + tail, table.dtype))
    if group == 1:
        return rows[:k]
    return rows.reshape((turns * per, group) + tail)[jnp.arange(k),
                                                     ids - first]

# (usage, platform, mesh?) -> refusal; a capability PROBE, not a
# platform allowlist: whether a backend that ACCEPTS the pinned_host
# placement can also run what the caller will do with the arrays is a
# property of the installed jax/backend pair, so it is probed with a
# tiny instance of that usage instead of hardcoding a platform string.
# Probed per sharding FORM (single-device vs mesh NamedSharding) because
# the two can differ. Value: None when usable, else the refusal.
_REFUSAL: dict = {}


def _probe_gather(host, main):
    """What the feature tiers do: gather host rows by device ids."""
    import numpy as np
    ids = jax.device_put(np.array([5, 1], np.int32), main.sharding)
    got = np.asarray(jax.jit(take_rows)(host.reshape(8, 1), ids))
    if got.tolist() != [[5.0], [1.0]]:
        raise NotImplementedError(f"host gather returned {got}")


def _probe_mixed(host, main):
    """What the HOST-mode sampler does: ordinary device ops straight
    over host-space and device-space operands."""
    float(jax.jit(lambda h, m: (h + m).sum())(host, main))


_PROBES = {"gather": _probe_gather, "mixed": _probe_mixed}


def _definitive(e: Exception) -> bool:
    """True when the failure is the compile/placement capability gap
    itself (cacheable), not a transient backend error that would
    otherwise lock a long-lived process into the fallback regime."""
    msg = str(e).lower()
    return isinstance(e, NotImplementedError) or \
        "memory_space" in msg or "memory kind" in msg or \
        "memory_kind" in msg or "pinned_host" in msg


def _host_offload_refusal(usage, dev, mesh=None):
    """None when ``usage`` of pinned host arrays works here, else why
    it does not (the backend's or jax's own words)."""
    key = (usage, getattr(dev, "platform", None), mesh is not None)
    if key not in _REFUSAL:
        import numpy as np
        try:
            if mesh is not None:
                sh = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(),
                    memory_kind="pinned_host")
                main_sh = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())
            else:
                sh = jax.sharding.SingleDeviceSharding(
                    dev, memory_kind="pinned_host")
                main_sh = jax.sharding.SingleDeviceSharding(dev)
            _PROBES[usage](
                jax.device_put(np.arange(8, dtype=np.float32), sh),
                jax.device_put(np.ones((8,), np.float32), main_sh))
            _REFUSAL[key] = None
        except Exception as e:  # noqa: BLE001 - classify, maybe cache
            why = f"{type(e).__name__}: {e}"
            if not _definitive(e):
                return why      # transient: fail this call, don't cache
            _REFUSAL[key] = why
    return _REFUSAL[key]


def pinned_put(arrays, dev, allow_fallback, what, mesh=None,
               usage: str = "mixed"):
    """Place ``arrays`` on pinned host memory. Returns the placed list,
    or None after a LOUD log when ``allow_fallback`` and the placement
    is unusable; raises otherwise.

    ``usage`` names what the caller will do with the arrays inside jit,
    which is what gets probed: ``"gather"`` (rows through ``take_rows``,
    the feature tiers) or ``"mixed"`` (device ops straight over them,
    the HOST-mode sampler — which jax 0.9.0 refuses on every backend).

    With ``mesh`` the arrays are placed host-replicated over the mesh
    (``NamedSharding(mesh, P(), memory_kind='pinned_host')``) so they
    can feed computations whose other operands are mesh-sharded —
    single-device pinned arrays and mesh-sharded arrays have
    incompatible device sets and fail at dispatch.

    Usability is established by ``_host_offload_refusal``'s probe (one
    tiny instance of the usage per platform, cached)."""
    try:
        probe_dev = mesh.devices.flat[0] if mesh is not None else dev
        why = _host_offload_refusal(usage, probe_dev, mesh=mesh)
        if why is not None:
            raise NotImplementedError(
                f"pinned_host arrays cannot be used this way here "
                f"(probed {usage!r}: {why[:500]})")
        if mesh is not None:
            sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(),
                memory_kind="pinned_host")
        else:
            sh = jax.sharding.SingleDeviceSharding(
                dev, memory_kind="pinned_host")
        return [jax.device_put(a, sh) for a in arrays]
    except (ValueError, NotImplementedError) as e:
        if not allow_fallback:
            raise ValueError(
                "no usable 'pinned_host' memory kind here "
                f"(placing {what}): {e}. Default placement is a "
                "different performance regime — pass allow_fallback="
                "True to accept it") from e
        _log("no usable 'pinned_host' memory kind on this backend; "
             "%s falls back to default placement (a different "
             "performance regime)", what)
        return None
