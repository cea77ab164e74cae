"""jit'd public wrappers around the Pallas kernels.

``summarize_pallas`` is the full TPU Summarizer pipeline: bitonic-sort VMEM
tiles → per-tile exact histograms → merge (optionally via the fused merge
kernel).  ``interpret=None`` (the default) runs the kernel bodies in the
Pallas interpreter on the CPU and compiles them on any other backend
(``tile_sort.resolve_interpret``).  None of them lowers for the TPU yet:
Mosaic has no rule for ``rev`` (the bitonic partner exchange) nor for
``dynamic_slice`` (``bucket_count_kernel``), so on a TPU these wrappers
raise ``NotImplementedError`` instead of running.  The served path
(``core/``) never calls them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.histogram import Histogram, merge
from repro.kernels.bucket_count import cumulative_counts_pallas
from repro.kernels.merge_cut import merge_pallas
from repro.kernels.ref import bucket_sizes_from_cumulative
from repro.kernels.tile_sort import pad_to_tiles, sort_tiles_pallas

__all__ = [
    "bucket_sizes_pallas",
    "summarize_pallas",
    "merge_histograms_pallas",
]


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def bucket_sizes_pallas(
    x: jax.Array,
    boundaries: jax.Array,
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """True per-bucket counts of ``x`` under ``boundaries`` (validation op)."""
    cum = cumulative_counts_pallas(
        x, boundaries, block_rows=block_rows, interpret=interpret
    )
    return bucket_sizes_from_cumulative(cum)


def _tile_histograms(
    sorted_tiles: jax.Array, T: int, n: int | None = None
) -> Histogram:
    """Exact T-bucket histograms of each (already sorted) tile row.

    ``n`` is the total number of *real* values when the last tile carries a
    sentinel-padded ragged tail (``pad_to_tiles``): that tile's cut indices
    are computed from its true prefix length, so the padding never enters a
    boundary or a bucket count.  Cut indices are static (host-side integer
    arithmetic — exact floors, no float rounding).
    """
    tiles, tile_len = sorted_tiles.shape
    if n is None:
        n = tiles * tile_len
    n_i = np.minimum(
        tile_len, n - np.arange(tiles, dtype=np.int64) * tile_len
    )  # true values per tile; only the last can be short, never 0
    i = np.arange(T + 1, dtype=np.int64)
    cuts = (i[None, :] * n_i[:, None]) // T  # (tiles, T+1), exact floor
    idx = np.minimum(cuts, n_i[:, None] - 1).astype(np.int32)
    boundaries = jnp.take_along_axis(sorted_tiles, jnp.asarray(idx), axis=1)
    sizes = jnp.asarray(np.diff(cuts, axis=1).astype(np.float32))
    return Histogram(boundaries=boundaries, sizes=sizes)


@functools.partial(
    jax.jit, static_argnames=("tile_len", "T_tile", "T_out", "interpret", "fused_merge")
)
def summarize_pallas(
    x: jax.Array,
    *,
    tile_len: int = 4096,
    T_tile: int = 256,
    T_out: int = 1024,
    interpret: bool | None = None,
    fused_merge: bool = True,
) -> Histogram:
    """TPU Summarizer: tile-sort kernel + paper-merge of the tile summaries.

    Error vs. a fully exact histogram is bounded by the hierarchy composition
    (DESIGN.md §5): ``< 2n/T_tile`` from the tile level (the T_out-level
    output is itself a merge product; the Theorem-1 bound holds for unequal
    tile sizes, so a ragged last tile does not loosen it).  Ragged input
    lengths are handled by sentinel-padding the tail tile and masking its
    cut indices — no multiple-of-``tile_len`` requirement.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    assert n >= 1, "cannot summarize an empty array"
    flat = pad_to_tiles(flat, tile_len)
    xt = flat.reshape(flat.shape[0] // tile_len, tile_len)
    sorted_tiles = sort_tiles_pallas(xt, interpret=interpret)
    tiles_h = _tile_histograms(sorted_tiles, T_tile, n)
    if fused_merge:
        b, s = merge_pallas(
            tiles_h.boundaries, tiles_h.sizes, T_out, interpret=interpret
        )
        return Histogram(boundaries=b, sizes=s)
    return merge(tiles_h, T_out)


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def merge_histograms_pallas(
    stacked: Histogram, beta: int, *, interpret: bool | None = None
) -> Histogram:
    """Fused Merger kernel over stacked summaries (k, T+1)/(k, T)."""
    b, s = merge_pallas(
        stacked.boundaries, stacked.sizes, beta, interpret=interpret
    )
    return Histogram(boundaries=b, sizes=s)
