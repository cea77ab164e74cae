"""Pallas TPU kernel: streaming bucket counting against fixed boundaries.

The validation/query hot spot of the histogram framework: given a boundary
sequence ``b_0..b_T`` and a large value stream, count how many values fall in
every bucket.  Used by (a) the exactness checker (μ_s measurement needs true
bucket sizes under approximate boundaries), (b) range-count queries, and
(c) quantization calibration.

TPU adaptation (vs. the scalar binary-search a CPU implementation would use):
no data-dependent control flow and no scatter.  Each grid step stages one
``(block_rows, 128)`` tile of the stream into VMEM and compares it against
the full boundary vector (also VMEM-resident, ``T ≤ 2048`` boundaries ⇒
≤8 KiB) with one broadcast ``(tile, T+1)`` less-than, reduced over the tile —
a pure VPU workload with arithmetic intensity ``T`` ops/byte, far above the
roofline knee for ``T ≥ 64``.  The per-bucket counts are the first
difference of the cumulative counts, taken by the wrapper.

Grid steps on TPU execute sequentially per core, so the kernel accumulates
partial counts into the output block across steps (the standard revisited-
output reduction pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tile_sort import resolve_interpret

__all__ = ["bucket_count_kernel", "cumulative_counts_pallas"]

LANE = 128  # TPU vector lane width; last dim of every VMEM tile


def bucket_count_kernel(x_ref, b_ref, out_ref):
    """One grid step: fold one VMEM tile of values into cumulative counts.

    out[: T+1] — # of values  < b_j   (cumulative counts)
    out[T+1]   — # of values == b_T   (paper: last bucket is right-closed)
    """
    i = pl.program_id(0)
    x = x_ref[...].reshape(-1, 1)  # (tile, 1)
    b = b_ref[...].reshape(1, -1)  # (1, T+1)
    lt = (x < b).astype(jnp.float32)
    partial_cum = jnp.sum(lt, axis=0)  # (T+1,)
    eq_last = jnp.sum((x[:, 0] == b[0, -1]).astype(jnp.float32))
    partial = jnp.concatenate([partial_cum, eq_last[None]])

    @pl.when(i == 0)
    def _init():
        out_ref[...] = partial.reshape(out_ref.shape)

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial.reshape(out_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def cumulative_counts_pallas(
    x: jax.Array,
    boundaries: jax.Array,
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Cumulative ``< b_j`` counts of ``x`` (any shape) + ``== b_T`` count.

    Returns shape ``(T+2,)`` float32.  ``x`` is padded to a whole number of
    ``(block_rows, 128)`` tiles with ``+inf`` (never counted: strictly above
    every boundary and ``!= b_T``).
    """
    flat = x.reshape(-1).astype(jnp.float32)
    tile = block_rows * LANE
    n = flat.shape[0]
    n_pad = (-n) % tile
    flat = jnp.pad(flat, (0, n_pad), constant_values=jnp.inf)
    blocks = flat.shape[0] // tile
    xt = flat.reshape(blocks, block_rows, LANE)
    b = boundaries.astype(jnp.float32)
    T1 = b.shape[0]

    out = pl.pallas_call(
        bucket_count_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((T1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((T1 + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((T1 + 1,), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xt, b)
    return out
