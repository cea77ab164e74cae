"""The served programs compile for a TPU v5e at deployment widths.

Each program is compiled for a described (not attached) v5e chip, so these
tests run on the CPU and cost no chip time.  Widths are the service's at
the paper's configuration (``configs/paper_logstats.py``): β = 254,
T = 8β = 2,032, 4,096-value windows, 256-panel ``query_many`` batches over
64 tenants × 512 windows.  The Pallas kernels are off the served path and
do not lower for the TPU yet; each refusal is recorded as a strict xfail
naming the primitive Mosaic has no rule for.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and test
collection happens in every worker.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.histogram import build_exact_padded_batched
from repro.core.interval_tree import _gather_rows, merge_stacks
from repro.kernels import (
    cumulative_counts_pallas,
    merge_pallas,
    sort_kv_pallas,
    sort_tiles_pallas,
)

BETA = 254
T = 8 * BETA
WINDOW = 4096  # values per window
ROWS = 256  # summarizer rows per dispatch (core/stream.py _BATCH_ROWS)
PANELS = 256
K_PAD = 16  # canonical nodes of a range within 512 windows, at most 16
PLANE_ROWS = 65_536  # pow2 capacity holding 64 × (512 leaves + 511 nodes)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_summarizer_compiles_for_v5e(one_chip):
    _fits(
        build_exact_padded_batched.lower(
            _spec(one_chip, (ROWS, WINDOW)),
            _spec(one_chip, (ROWS,), jnp.int32),
            num_buckets=T,
        ).compile()
    )


# one panel's pre-histogram holds k_pad·(T+1) boundaries, a batch Q of them
PRE_HISTOGRAM = PANELS * K_PAD * (T + 1)


def _gather_sizes(hlo_text: str) -> list[int]:
    """Element count of every gather's output in an HLO module's text, in
    either form: StableHLO (``"stablehlo.gather"(...) ... -> tensor<AxBxf32>``)
    or a compiled module (``%gather.3 = f32[A,B]{1,0} gather(...)``, fused
    computations included)."""
    sizes = []
    for line in hlo_text.splitlines():
        if '"stablehlo.gather"' in line:
            m = re.search(r"->\s*tensor<([\dx]+)x[a-z]\w*>", line)
        elif re.search(r"\bgather\(", line):
            m = re.search(r"=\s*[a-z]\w*\[([\d,]*)\]", line)
        else:
            continue
        assert m, line
        dims = [int(d) for d in re.split(r"[x,]", m.group(1)) if d]
        sizes.append(math.prod(dims))
    return sizes


def _no_pre_histogram_gather(hlo_text: str) -> None:
    # the pre-histogram's boundaries and masses come out of one sort; a
    # gather of its full length would be a permutation by an argsort
    sizes = _gather_sizes(hlo_text)
    assert PRE_HISTOGRAM not in sizes, sizes


def _no_while(hlo_text: str) -> None:
    # the cut is a count with no binary search: a `while` would be the
    # searchsorted loop of one scalar gather a step
    loops = [line for line in hlo_text.splitlines() if re.search(r"\bwhile\b", line)]
    assert not loops, loops[:3]


def test_query_merge_compiles_for_v5e(one_chip):
    compiled = merge_stacks.lower(
        _spec(one_chip, (PANELS, K_PAD, T + 1)),
        _spec(one_chip, (PANELS, K_PAD, T)),
        beta=BETA,
    ).compile()
    _fits(compiled)
    _no_pre_histogram_gather(compiled.as_text())
    _no_while(compiled.as_text())


def test_planner_merge_compiles_for_v5e_without_a_loop(one_chip):
    # the benchmark planner's merge shape: 256 panels of day ranges within
    # one month, each of at most 8 canonical nodes
    compiled = merge_stacks.lower(
        _spec(one_chip, (PANELS, 8, T + 1)),
        _spec(one_chip, (PANELS, 8, T)),
        beta=BETA,
    ).compile()
    _no_while(compiled.as_text())


def test_query_merge_lowers_without_pre_histogram_gather():
    # the same checks on the portable lowering, where no v5e can be described
    lowered = merge_stacks.lower(
        jax.ShapeDtypeStruct((PANELS, K_PAD, T + 1), jnp.float32),
        jax.ShapeDtypeStruct((PANELS, K_PAD, T), jnp.float32),
        beta=BETA,
    )
    _no_pre_histogram_gather(lowered.as_text())
    _no_while(lowered.as_text())


def test_arena_gather_compiles_for_v5e(one_chip):
    _fits(
        _gather_rows.lower(
            _spec(one_chip, (PLANE_ROWS, T + 1)),
            _spec(one_chip, (PLANE_ROWS, T)),
            _spec(one_chip, (PANELS, K_PAD), jnp.int32),
            _spec(one_chip, (PANELS, K_PAD)),
        ).compile()
    )


def _refused(primitive: str):
    return pytest.mark.xfail(
        strict=True,
        raises=NotImplementedError,
        reason=f"Pallas TPU lowering has no rule for `{primitive}`",
    )


@pytest.mark.parametrize(
    "primitive, compile_kernel",
    [
        pytest.param(
            "rev",
            lambda s: sort_tiles_pallas.lower(
                _spec(s, (4, WINDOW)), interpret=False
            ).compile(),
            marks=_refused("rev"),
            id="sort_tiles_pallas",
        ),
        pytest.param(
            "rev",
            lambda s: sort_kv_pallas.lower(
                _spec(s, (4, WINDOW)), _spec(s, (4, WINDOW)), interpret=False
            ).compile(),
            marks=_refused("rev"),
            id="sort_kv_pallas",
        ),
        pytest.param(
            "rev",
            lambda s: merge_pallas.lower(
                _spec(s, (8, 257)), _spec(s, (8, 256)), 64, interpret=False
            ).compile(),
            marks=_refused("rev"),
            id="merge_pallas",
        ),
        pytest.param(
            "dynamic_slice",
            lambda s: cumulative_counts_pallas.lower(
                _spec(s, (8192,)), _spec(s, (257,)), interpret=False
            ).compile(),
            marks=_refused("dynamic_slice"),
            id="cumulative_counts_pallas",
        ),
    ],
)
def test_pallas_kernel_lowers_for_v5e(one_chip, primitive, compile_kernel):
    try:
        compile_kernel(one_chip)
    except NotImplementedError as e:
        # the refusal must be the recorded one, not some other gap
        assert f": {primitive}." in str(e), str(e)
        raise
