"""Main-path kernels compile for a TPU v5e at real widths.

Each test lowers one Pallas kernel of the device read path and compiles
it with the TPU compiler for a described (not attached) ``v5e:2x2``
topology, at the paper's largest deployment: TPC-H ``orders`` at scale
factor 5 (7.5 M rows), 8 int32 key lanes and an 8-row float32 value tile,
``DEVICE_BLOCK_N`` rows per block, and the query chunks the wrappers
really launch. The compiler refuses here what the chip would refuse —
unsupported primitives in the Mosaic lowering, too much VMEM — at no
chip time. Nothing runs, so these tests say nothing about results or
speed; the interpret-mode tests own correctness.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, and under xdist
every worker imports this file.
"""

from __future__ import annotations

import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import DEVICE_BLOCK_N, SELECT_COMPACT_MAX_ELEMS
from repro.kernels.scan_agg import query_chunk

N_ROWS = -(-7_500_000 // DEVICE_BLOCK_N) * DEVICE_BLOCK_N  # SF 5, padded
K_PAD = V_PAD = 8  # resident key lanes / value-tile rows
N_VALS = 3  # totalprice, shippriority, the ones row
CHUNK = query_chunk(DEVICE_BLOCK_N)

# by module path: ``repro.kernels.ecdf_hist`` the attribute is the
# function the package re-exports, not the module
slab_locate = importlib.import_module("repro.kernels.slab_locate")
merge_runs = importlib.import_module("repro.kernels.merge_runs")
block_agg = importlib.import_module("repro.kernels.block_agg")
ecdf_hist = importlib.import_module("repro.kernels.ecdf_hist")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described chip, with JAX's
    persistent cache off: a compile for a described chip is written to
    it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype=jnp.int32: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiles_to_kernel(lowered) -> None:
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_query_chunk_is_the_vmem_bound():
    assert CHUNK == 128  # compiles below; 256 runs out of VMEM at 8192 rows


@pytest.mark.parametrize("q", [64, CHUNK])
def test_fused_scan(shape, q):
    S = shape
    b = S((q, 3))
    _compiles_to_kernel(
        slab_locate._fused_call.lower(
            S((K_PAD, N_ROWS)), S((V_PAD, N_ROWS), jnp.float32), b, b, b, b,
            S((q, 2)), S((q,)), col_parts=(1, 1, 1), n_vals=N_VALS,
            block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


def test_fused_scan_wide_key(shape):
    S = shape
    b = S((64, 4))
    _compiles_to_kernel(
        slab_locate._fused_call.lower(
            S((K_PAD, N_ROWS)), S((V_PAD, N_ROWS), jnp.float32), b, b, b, b,
            S((64, 2)), S((64,)), col_parts=(2, 1, 1), n_vals=N_VALS,
            block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


def test_slab_locate(shape):
    S = shape
    b = S((CHUNK, 3))
    _compiles_to_kernel(
        slab_locate._slab_locate_call.lower(
            S((K_PAD, N_ROWS)), b, b, S((CHUNK, 2)), n_lanes=3,
            block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


@pytest.mark.parametrize("q,width", [(64, 128), (CHUNK, SELECT_COMPACT_MAX_ELEMS // CHUNK)])
def test_select_compact(shape, q, width):
    S = shape
    b = S((q, 3))
    _compiles_to_kernel(
        slab_locate._select_call.lower(
            S((K_PAD, N_ROWS)), b, b, S((q, 2)), col_parts=(1, 1, 1),
            out_width=width, block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


@pytest.mark.parametrize("q", [64, CHUNK])
def test_select_sparse(shape, q):
    S = shape
    _compiles_to_kernel(
        slab_locate._select_sparse_call.lower(
            S((K_PAD, N_ROWS)), S((q, 2 * 3 + 4)), col_parts=(1, 1, 1),
            out_width=128, steps=2, interpret=False,
        )
    )


def test_merge_rank(shape):
    S = shape
    w = S((CHUNK, 2))
    _compiles_to_kernel(
        merge_runs._merge_rank_call.lower(
            S((K_PAD, N_ROWS)), S((CHUNK, 3)), w, w, n_lanes=3, row_off=0,
            block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


def test_block_sums_and_boundary(shape):
    S = shape
    vals = S((V_PAD, N_ROWS), jnp.float32)
    _compiles_to_kernel(
        block_agg._block_sums_call.lower(vals, block_n=DEVICE_BLOCK_N, interpret=False)
    )
    _compiles_to_kernel(
        block_agg._boundary_call.lower(
            vals, S((64,)), S((64,)), S((64, 8, 128)), n_win=3,
            block_n=DEVICE_BLOCK_N, interpret=False,
        )
    )


def test_ecdf_hist_4096_bins(shape):
    _compiles_to_kernel(
        ecdf_hist.ecdf_hist_pallas.lower(
            shape((N_ROWS,)), n_bins=4096, bin_width=1, interpret=False
        )
    )
