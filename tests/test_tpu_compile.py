"""The storage path's Pallas kernels and jitted scans compile for a TPU v5e.

Each test lowers a program for one chip of a described (not attached)
``v5e:2x2`` topology at the widths ``chip_smoke.py`` runs, and compiles it
with the TPU compiler: a kernel that only passes in interpret mode (an
unaligned slice, a scalar store to VMEM, a block over the scoped VMEM
limit) fails here. Where a Pallas kernel is expected, the compiled HLO
must contain it as a ``tpu_custom_call``. Nothing runs.

The topology is described inside a fixture, never while the module is
imported: only the process that runs these tests loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import optassign
from repro.kernels import entropy_features, overlap

HBM_BYTES = 16 * 2**30                       # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiling for a described topology attaches no chip, so test
    # workers may each load the TPU library without its one-process lock
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled.as_text()


# TPC-H SF 1 with the paper's 440-query log: 217 query families over
# ~17.3k files; the widest family (an l_shipmode scan) touches all 12,000
# lineitem files, padded to 12,032 codes. N=1024, m=64 is a wide, shallow
# family set.
@pytest.mark.parametrize("n,m,n_files", [(217, 12032, 17320),
                                         (1024, 64, 16384)])
def test_overlap_kernel_compiles(one_chip, n, m, n_files):
    hlo = _compile(overlap.fractional_overlap_matrix, one_chip,
                   ((n, m), jnp.int32), ((n_files,), jnp.float32),
                   ((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


# The float class of TPC-H SF 0.1 after G-PART: 12 partitions, up to 1.8M
# values each, a 583,182-entry local vocabulary (583,296 lane-padded).
@pytest.mark.parametrize("n_buckets", [1, 5])
def test_entropy_kernel_compiles(one_chip, n_buckets):
    def fn(codes, n_valid, n_rows, n_cols, lengths):
        return entropy_features.weighted_entropy_features(
            codes, n_valid, n_rows, n_cols, lengths, n_buckets=n_buckets)
    n = ((12,), jnp.int32)
    hlo = _compile(fn, one_chip, ((12, 1_800_000), jnp.int32), n, n, n,
                   ((12, 583_296), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_byte_entropy_compiles(one_chip):
    hlo = _compile(entropy_features.byte_entropy, one_chip,
                   ((1 << 20,), jnp.uint8))
    assert "tpu_custom_call" in hlo


# bench_fleet tenants: N_t in [12, 47], L=4 tiers, K=3 schemes. Shared
# capacity rows run the general scan over all T tenants; uncoupled fleets
# run the lean scan in fixed 64-tenant chunks.
def test_fleet_scan_compiles_at_t256(one_chip):
    T, N, L, K = 256, 47, 4, 3
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_

    def general(*a):
        return optassign._fleet_scan_single(*a, iters=200)

    _compile(general, one_chip, ((T, N, L, K), f32), ((T, N, L, K), f32),
             ((T, L), f32), ((T, L), b), ((L,), i32), ((T, 1), f32),
             ((T, 1), b), ((L,), i32), ((L,), f32), ((L,), b), ((T,), f32),
             ((), f32))

    def lean(*a):
        return optassign._fleet_scan_plain(*a, iters=200)

    C = optassign._FLEET_CHUNK
    _compile(lean, one_chip, ((C, N, L, K), f32), ((C, N, L, K), f32),
             ((C, L), f32), ((C, L), b), ((C,), f32))
