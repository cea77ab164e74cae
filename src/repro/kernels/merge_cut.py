"""Pallas TPU kernel: fused histogram merge (paper Algorithm 1, one shot).

Fuses the whole Merger into a single VMEM-resident kernel:

    sort boundaries (bitonic, key=boundary value, payload=bucket mass)
  → left-collapse cumulative sizes A  (Hillis–Steele log-depth prefix sum —
    shift+add vector ops, no serial scan)
  → cut selection: cut_j = Σ 1[A ≤ j·N/β]  (broadcast compare + row reduce,
    the batched form of `searchsorted(A, t, 'right')`)
  → boundary/prefix gather at the cuts as one-hot matmuls (MXU work, no
    dynamic gather).

Input is the flat concatenation of ``k`` summaries padded to a power of two
with ``+inf`` boundaries / zero mass; the pad sorts to the tail and carries
no mass, so A and the cuts are unaffected.  The last *real* boundary (the
global max) is selected with a one-hot at index ``L_real - 1``.

Everything is ``O(L log² L)`` vector work on a problem of size
``L = k(T+1)`` ≤ a few hundred KiB — one VMEM residence, zero HBM round
trips between the stages the unfused JAX path would take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tile_sort import _bitonic_kv, resolve_interpret

__all__ = ["merge_cut_kernel", "merge_pallas"]


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Hillis–Steele inclusive prefix sum: log2(n) shift+add stages."""
    n = x.shape[0]
    d = 1
    while d < n:
        shifted = jnp.pad(x, (d, 0))[:n]
        x = x + shifted
        d *= 2
    return x


def merge_cut_kernel(b_ref, m_ref, t_ref, last_ref, bo_ref, so_ref):
    pos = b_ref[...].reshape(-1)  # (L,) padded boundaries
    mass = m_ref[...].reshape(-1)  # (L,) aligned masses (0 for pads)
    targets = t_ref[...].reshape(-1)  # (β-1,) = j·N/β
    L = pos.shape[0]

    pos, mass = _bitonic_kv(pos, mass)
    cum = _prefix_sum(mass)  # (L,)  cum[i] = CDF at pos[i]
    # A[m] = A(m+1, H⁰) = cum[m]; valid for m in [0, L-2] (length L-1).
    # cut_j = #{m : A[m] <= t_j}  over the valid range.
    idx = jax.lax.iota(jnp.int32, L)
    a_valid = (idx < L - 1)
    le = (cum[None, :] <= targets[:, None]) & a_valid[None, :]
    cut = jnp.sum(le.astype(jnp.int32), axis=1)  # (β-1,) in [0, L-1]

    # interior boundaries: pos[cut]  (one-hot @ pos — MXU, no gather).
    # The +inf pads must be masked first: one-hot zeros times inf give NaN.
    pos_finite = jnp.where(jnp.isfinite(pos), pos, jnp.float32(0))
    onehot_cut = (idx[None, :] == cut[:, None]).astype(pos.dtype)
    interior = onehot_cut @ pos_finite
    # prefix size at the cut: cum[cut-1], 0 when cut == 0
    onehot_prev = (idx[None, :] == (cut[:, None] - 1)).astype(pos.dtype)
    s_at_cut = onehot_prev @ cum

    n_total = cum[L - 1]
    last_idx = last_ref[0] - 1  # L_real - 1: the global max boundary
    onehot_last = (idx == last_idx).astype(pos.dtype)
    b_last = jnp.sum(onehot_last * pos_finite)

    beta = so_ref.shape[-1]
    full = jnp.concatenate(
        [jnp.zeros((1,), cum.dtype), s_at_cut, n_total[None]]
    )
    bo = jnp.concatenate([pos[:1], interior, b_last[None]])
    bo_ref[...] = bo.reshape(bo_ref.shape)
    so_ref[...] = (full[1:] - full[:-1]).reshape(so_ref.shape)
    del beta


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def merge_pallas(
    boundaries: jax.Array,
    sizes: jax.Array,
    beta: int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Merge stacked summaries ``boundaries (k, T+1)``, ``sizes (k, T)``.

    Returns ``(merged_boundaries (β+1,), merged_sizes (β,))`` — the fused
    equivalent of :func:`repro.core.histogram.merge`.
    """
    k, T1 = boundaries.shape
    if beta == 1:  # degenerate: one bucket spanning [min, max] — no cuts
        b = boundaries.astype(jnp.float32)
        return (
            jnp.stack([jnp.min(b), jnp.max(b)]),
            jnp.sum(sizes.astype(jnp.float32))[None],
        )
    mass = jnp.concatenate(
        [sizes.astype(jnp.float32), jnp.zeros((k, 1), jnp.float32)], axis=-1
    ).reshape(-1)
    flat = boundaries.astype(jnp.float32).reshape(-1)
    L_real = flat.shape[0]
    L = 1 << (L_real - 1).bit_length()  # next power of two
    flat = jnp.pad(flat, (0, L - L_real), constant_values=jnp.inf)
    mass = jnp.pad(mass, (0, L - L_real))
    n = jnp.sum(mass)
    targets = jnp.arange(1, beta, dtype=jnp.float32) * (n / beta)
    last = jnp.asarray([L_real], dtype=jnp.int32)

    bo, so = pl.pallas_call(
        merge_cut_kernel,
        in_specs=[
            pl.BlockSpec(flat.shape, lambda: tuple(0 for _ in flat.shape)),
            pl.BlockSpec(mass.shape, lambda: (0,)),
            pl.BlockSpec(targets.shape, lambda: (0,)),
            pl.BlockSpec((1,), lambda: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((beta + 1,), lambda: (0,)),
            pl.BlockSpec((beta,), lambda: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((beta + 1,), jnp.float32),
            jax.ShapeDtypeStruct((beta,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(flat, mass, targets, last)
    return bo, so
