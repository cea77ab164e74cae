"""Pallas TPU kernel: bitonic sorting network over VMEM tiles.

The Summarizer's cost is the partition sort.  A global HBM-resident sort is
the wrong algorithm on TPU (no efficient scatter, expensive data-dependent
movement); instead we sort *tiles that fit VMEM* with a bitonic network —
``log²`` compare-exchange stages of pure vector min/max/select, zero
data-dependent control flow, perfectly pipelineable — and let the *paper's
own merge theorem* combine per-tile exact histograms into the device summary
(kernels/ops.py::summarize_pallas).  This is the paper's insight recursed
one level down the memory hierarchy: HDFS partition → HBM shard → VMEM tile.

The compare-exchange partner ``i ^ j`` is realized as a reshape + reverse of
the trailing block pair rather than a gather.  Mosaic has no lowering rule
for that reverse (``rev``), so these kernels do not compile for the TPU yet
(tests/test_chip_compile.py records the refusal).

Key-value variant (``tile_sort_kv_kernel``) carries a payload through the
network (used by the fused merge kernel to keep bucket masses aligned with
their boundaries).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "tile_sort_kernel",
    "tile_sort_kv_kernel",
    "sort_tiles_pallas",
    "sort_kv_pallas",
    "pad_to_tiles",
    "resolve_interpret",
]

LANE = 128


def resolve_interpret(interpret: bool | None) -> bool:
    """The ``interpret`` flag every kernel wrapper passes to ``pallas_call``.

    ``None`` (the wrappers' default) runs the kernel body in the Pallas
    interpreter on the CPU and compiles it on any other backend, so a TPU
    caller gets the Mosaic lowering — or its error — and never a silent
    interpreter run.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def pad_to_tiles(flat: jax.Array, tile_len: int) -> jax.Array:
    """Pad a 1-D array up to a whole number of tiles with a +inf sentinel.

    The sentinel (dtype max for integers) sorts past every real value, so a
    ragged tail becomes one partially-real tile whose true prefix length the
    caller masks out (kernels/ops.py) — the same padding contract as the
    shape-stable ``build_exact_padded`` (core/histogram.py).  The pad amount
    is static (derived from ``flat.shape``), so this composes with jit.
    """
    n = flat.shape[0]
    rem = (-n) % tile_len
    if rem == 0:
        return flat
    if jnp.issubdtype(flat.dtype, jnp.floating):
        fill = jnp.array(jnp.inf, flat.dtype)
    else:
        fill = jnp.array(jnp.iinfo(flat.dtype).max, flat.dtype)
    return jnp.concatenate([flat, jnp.full((rem,), fill, flat.dtype)])


def _bitonic(x: jax.Array) -> jax.Array:
    """Full ascending bitonic network on a power-of-two 1-D array."""
    n = x.shape[0]
    assert n & (n - 1) == 0, "bitonic network needs power-of-two length"
    idx = jax.lax.iota(jnp.int32, n)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            # partner value at index i^j via reshape+reverse (no gather)
            xp = x.reshape(-1, 2, j)[:, ::-1, :].reshape(n)
            up = (idx & k) == 0  # ascending region of this stage
            lower = (idx & j) == 0  # i < partner
            take_min = lower == up
            x = jnp.where(take_min, jnp.minimum(x, xp), jnp.maximum(x, xp))
            j //= 2
        k *= 2
    return x


def _bitonic_kv(key: jax.Array, val: jax.Array) -> tuple[jax.Array, jax.Array]:
    """STABLE bitonic network: sorts ``key`` carrying ``val`` alongside.

    Stability matters for bit-parity with the reference merge: at tied
    boundary values the left-collapse cumulative masses within the tie
    group depend on visit order, and a rank-select cut landing inside the
    group would otherwise report (bound-compliant but) different bucket
    sizes than the stable-argsort reference.  The network therefore sorts
    the lexicographic pair (key, original_index) — a total order, so the
    result is exactly ``jnp.argsort(key, stable=True)`` applied to both
    arrays.
    """
    n = key.shape[0]
    assert n & (n - 1) == 0
    pos = jax.lax.iota(jnp.int32, n)
    tag = jax.lax.iota(jnp.int32, n)  # original index, travels with element
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            kp = key.reshape(-1, 2, j)[:, ::-1, :].reshape(n)
            vp = val.reshape(-1, 2, j)[:, ::-1, :].reshape(n)
            tp = tag.reshape(-1, 2, j)[:, ::-1, :].reshape(n)
            up = (pos & k) == 0
            lower = (pos & j) == 0
            take_min = lower == up
            # lexicographic (key, tag) comparison; min-role keeps on <=,
            # max-role on >= — (key, tag) pairs are unique so exactly one
            # side exchanges and no payload is duplicated or dropped.
            ties = key == kp
            lex_le = (key < kp) | (ties & (tag <= tp))
            lex_ge = (key > kp) | (ties & (tag >= tp))
            keep = jnp.where(take_min, lex_le, lex_ge)
            key = jnp.where(keep, key, kp)
            val = jnp.where(keep, val, vp)
            tag = jnp.where(keep, tag, tp)
            j //= 2
        k *= 2
    return key, val


def tile_sort_kernel(x_ref, o_ref):
    """Sort one VMEM tile ascending (tile = whole block, flattened)."""
    x = x_ref[...].reshape(-1)
    o_ref[...] = _bitonic(x).reshape(o_ref.shape)


def tile_sort_kv_kernel(k_ref, v_ref, ko_ref, vo_ref):
    k, v = _bitonic_kv(k_ref[...].reshape(-1), v_ref[...].reshape(-1))
    ko_ref[...] = k.reshape(ko_ref.shape)
    vo_ref[...] = v.reshape(vo_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_tiles_pallas(xt: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Sort each row of ``(tiles, tile_len)`` independently.

    ``tile_len`` must be a power of two and a multiple of 128 (one VMEM tile
    of shape ``(tile_len/128, 128)`` per grid step).
    """
    tiles, tile_len = xt.shape
    assert tile_len % LANE == 0 and tile_len & (tile_len - 1) == 0
    rows = tile_len // LANE
    xr = xt.reshape(tiles, rows, LANE)
    out = pl.pallas_call(
        tile_sort_kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, rows, LANE), xt.dtype),
        interpret=resolve_interpret(interpret),
    )(xr)
    return out.reshape(tiles, tile_len)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_kv_pallas(
    keys: jax.Array, vals: jax.Array, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Row-wise key-value sort of ``(tiles, tile_len)`` pairs."""
    tiles, tile_len = keys.shape
    assert tile_len % LANE == 0 and tile_len & (tile_len - 1) == 0
    rows = tile_len // LANE
    kr = keys.reshape(tiles, rows, LANE)
    vr = vals.reshape(tiles, rows, LANE)
    ko, vo = pl.pallas_call(
        tile_sort_kv_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, rows, LANE), keys.dtype),
            jax.ShapeDtypeStruct((tiles, rows, LANE), vals.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(kr, vr)
    return ko.reshape(tiles, tile_len), vo.reshape(tiles, tile_len)
