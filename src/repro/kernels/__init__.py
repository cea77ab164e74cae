"""Pallas TPU kernels for the histogram framework's compute hot spots.

tile_sort     — bitonic sorting network over VMEM tiles (the Summarizer sort)
bucket_count  — streaming boundary-comparison bucket counting (validation/query)
merge_cut     — fused Algorithm-1 merge: kv-sort + prefix-sum + rank-select

Validated on CPU in the Pallas interpreter against the ``ref.py`` oracles.
The ``interpret`` flag defaults to ``None``: interpreter on the CPU,
compiled elsewhere — where today they fail to lower (kernels/ops.py).
"""
from repro.kernels.bucket_count import cumulative_counts_pallas
from repro.kernels.merge_cut import merge_pallas
from repro.kernels.ops import (
    bucket_sizes_pallas,
    merge_histograms_pallas,
    summarize_pallas,
)
from repro.kernels.tile_sort import sort_kv_pallas, sort_tiles_pallas
from repro.kernels import ref

__all__ = [
    "cumulative_counts_pallas",
    "merge_pallas",
    "bucket_sizes_pallas",
    "merge_histograms_pallas",
    "summarize_pallas",
    "sort_kv_pallas",
    "sort_tiles_pallas",
    "ref",
]
