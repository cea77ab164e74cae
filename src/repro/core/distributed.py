"""Distributed summarize-and-merge — the paper's framework on a TPU mesh.

The Hadoop mapping (DESIGN.md §2):

    Summarizer job   →  per-device exact histogram of the local shard
                        (``shard_map`` + ``build_exact``; optionally the
                        Pallas tile-sort path, ``kernels/tile_sort``)
    summary files    →  ``(T+1)`` boundaries + ``T`` sizes per device
    Merger job       →  ``all_gather`` of the summaries (tiny) + vectorized
                        ``merge`` computed replicated on every device

Everything here composes with ``jax.jit`` under a mesh, so the training step
can call it inline (telemetry, quantile clipping) and XLA overlaps the
all-gather with surrounding compute.

Hierarchical merge (DESIGN.md §5): exact sorts only ever touch VMEM-tile-sized
blocks; the paper's own theorem is applied recursively tile → device → pod
with composed bound ``ε_total < 2N · Σ_level 1/T_level``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.histogram import (
    Histogram,
    build_exact,
    build_exact_batched,
    merge,
)

__all__ = [
    "local_summarize",
    "gather_and_merge",
    "distributed_histogram",
    "hierarchical_device_summary",
    "hierarchical_eps_bound",
    "distributed_histogram_hierarchical",
    "tensor_histogram_in_step",
]


def hierarchical_eps_bound(
    n: int,
    T_levels: Sequence[int],
    merges_k: Sequence[int] = (),
) -> float:
    """Composed Theorem-1 bound for a multi-level merge hierarchy.

    ``ε_total < 2N · Σ_level 1/T_level`` plus ``2k`` integer slack per merge
    of ``k`` inputs — the recursion used tile → device → pod here and across
    time by the segment-tree interval engine (``core/interval_tree.py``).
    """
    eps = 2.0 * n * sum(1.0 / T for T in T_levels)
    return eps + 2.0 * sum(merges_k)


def local_summarize(x_local: jax.Array, T: int) -> Histogram:
    """Summarizer: exact T-bucket histogram of this device's shard."""
    return build_exact(x_local.reshape(-1), T)


def gather_and_merge(
    local: Histogram, beta: int, axis_names: str | tuple[str, ...]
) -> Histogram:
    """Merger: all-gather per-device summaries along mesh axes and merge.

    Must run inside ``shard_map`` (or any context where ``axis_names`` are
    bound).  Moves ``k·(2T+1)`` scalars instead of ``N`` raw values — the
    paper's shuffle-avoidance, realized on ICI.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    b = local.boundaries
    s = local.sizes
    for ax in axis_names:
        b = jax.lax.all_gather(b, ax)
        s = jax.lax.all_gather(s, ax)
    b = b.reshape(-1, local.boundaries.shape[-1])
    s = s.reshape(-1, local.sizes.shape[-1])
    return merge(Histogram(b, s), beta)


def hierarchical_device_summary(
    x_local: jax.Array, tile_size: int, T_tile: int, T_device: int
) -> Histogram:
    """Tile-level summarize + merge on one device (level 0 of the hierarchy).

    The shard is cut into VMEM-sized tiles; each tile is summarized exactly
    (this is what the Pallas ``tile_sort`` kernel accelerates on real TPUs)
    and the per-tile summaries are merged into the device summary.  The tail
    that does not fill a tile forms one final smaller exact histogram.
    """
    flat = x_local.reshape(-1)
    n = flat.shape[0]
    n_tiles = n // tile_size
    if n_tiles == 0:
        return build_exact(flat, T_device)
    head = flat[: n_tiles * tile_size].reshape(n_tiles, tile_size)
    tiles = build_exact_batched(head, T_tile)
    rem = n - n_tiles * tile_size
    if rem > 0:
        tail = build_exact(flat[n_tiles * tile_size :], min(T_tile, rem))
        pad = T_tile - tail.sizes.shape[-1]
        tb = jnp.concatenate(
            [tail.boundaries, jnp.repeat(tail.boundaries[-1:], pad)]
        )
        ts = jnp.concatenate([tail.sizes, jnp.zeros((pad,), tail.sizes.dtype)])
        tiles = Histogram(
            jnp.concatenate([tiles.boundaries, tb[None]], axis=0),
            jnp.concatenate([tiles.sizes, ts[None]], axis=0),
        )
    return merge(tiles, T_device)


def _shard_map(fn, mesh, in_specs, out_specs):
    # Replication checking is off because the merged output is replicated
    # by construction (post-all_gather).
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def distributed_histogram(
    x: jax.Array,
    T: int,
    beta: int,
    mesh: jax.sharding.Mesh,
    axis_names: str | tuple[str, ...] = "data",
) -> Histogram:
    """β-bucket histogram of ``x`` sharded over ``axis_names``.

    ``x``: any-rank array whose leading dim is sharded over ``axis_names``.
    Returns a replicated :class:`Histogram`.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)

    def body(x_local):
        local = local_summarize(x_local, T)
        return gather_and_merge(local, beta, axis_names)

    spec = P(axis_names)
    out = _shard_map(
        body,
        mesh,
        in_specs=(spec,),
        out_specs=Histogram(P(), P()),
    )(x)
    return out


def distributed_histogram_hierarchical(
    x: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    tile_size: int = 8192,
    T_tile: int = 512,
    T_device: int = 4096,
    T_pod: int = 4096,
    beta: int = 254,
    data_axes: tuple[str, ...] = ("data",),
    pod_axis: str | None = "pod",
) -> Histogram:
    """Three-level tile → device → pod merge (DESIGN.md §5).

    Composed error bound: ``ε < 2N(1/T_tile + 1/T_device [+ 1/T_pod])``.
    When ``pod_axis`` is absent from the mesh the last level collapses.
    """
    axis_names = tuple(data_axes) + (
        (pod_axis,) if pod_axis and pod_axis in mesh.axis_names else ()
    )

    def body(x_local):
        dev = hierarchical_device_summary(x_local, tile_size, T_tile, T_device)
        if pod_axis and pod_axis in mesh.axis_names:
            mid = gather_and_merge(dev, T_pod, tuple(data_axes))
            return gather_and_merge(mid, beta, (pod_axis,))
        return gather_and_merge(dev, beta, tuple(data_axes))

    spec = P(axis_names)
    return _shard_map(
        body, mesh, in_specs=(spec,), out_specs=Histogram(P(), P())
    )(x)


def tensor_histogram_in_step(
    x: jax.Array,
    T: int,
    beta: int,
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
) -> Histogram:
    """Histogram of an arbitrary (possibly sharded) tensor inside a jitted step.

    Flattens, truncates the tail so the length divides the mesh size (< one
    element per device dropped — negligible for telemetry and documented),
    lays the flat vector out across all mesh axes and runs the paper's
    summarize+merge.  The all-gather is ``O(k·T)`` bytes, so per-step
    telemetry of every layer's gradients is affordable — this is the paper's
    "cheap statistics over partitioned data" applied to the optimizer plane.
    """
    k = 1
    for ax in axis_names:
        k *= mesh.shape[ax]
    flat = x.reshape(-1)
    n = flat.shape[0]
    usable = max((n // k) * k, 0)
    if usable < k:  # tiny tensor: replicate instead of sharding
        h = build_exact(flat.astype(jnp.float32), min(T, max(n, 1)))
        return h
    flat = jax.lax.with_sharding_constraint(
        flat[:usable].astype(jnp.float32),
        jax.sharding.NamedSharding(mesh, P(axis_names)),
    )

    def body(x_local):
        local = local_summarize(x_local, min(T, usable // k))
        return gather_and_merge(local, beta, axis_names)

    return _shard_map(
        body, mesh, in_specs=(P(axis_names),), out_specs=Histogram(P(), P())
    )(flat)
