"""Overlap-kernel property suite: the fractional-overlap matrix backends
(numpy / jnp-ref / pallas-interpret) and the PartitionIndex CSR core.

Each property runs over seeded random instances drawn by hypothesis:
symmetry, [0, 1] range, exact zero for disjoint
code sets (the PYTHONHASHSEED bug class from PR 2 — no fp residue may link
disjoint partitions), permutation invariance, cross-backend differentials
to 1e-5, and lossless ``Partition`` <-> ``PartitionIndex`` round-trip.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import datapart as dp
from repro.kernels import ops


def _instance(seed, n_parts=18, n_files=40, unit=False):
    rng = np.random.default_rng(seed)
    files = [f"t/{i}" for i in range(n_files)]
    sizes = {f: 1.0 if unit else float(rng.random() * 4 + 0.25)
             for f in files}
    qf = []
    for _ in range(n_parts):
        k = int(rng.integers(1, 7))
        fs = tuple(rng.choice(files, size=k, replace=False))
        qf.append((fs, float(rng.random() * 9 + 0.5)))
    return dp.make_partitions(qf, sizes)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_symmetry_and_range(seed):
    idx = dp.PartitionIndex.from_partitions(_instance(seed))
    w = idx.overlap_matrix("numpy")
    assert np.allclose(w, w.T, atol=0)
    assert (w >= 0.0).all() and (w <= 1.0 + 1e-6).all()
    # self-overlap is exactly 1
    assert np.allclose(np.diag(w), 1.0, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_disjoint_pairs_exact_zero(seed):
    """Partitions over disjoint file blocks: every cross weight must be
    exactly 0.0 in every backend — no summation-order residue."""
    rng = np.random.default_rng(seed)
    sizes = {f"t/{i}": float(rng.random() * 3 + 0.1) for i in range(60)}
    fs = dp.FileSizes(sizes)
    parts = [dp.Partition(frozenset(f"t/{j}" for j in range(10 * i, 10 * i + 10)),
                          1.0 + i, fs) for i in range(6)]
    idx = dp.PartitionIndex.from_partitions(parts)
    for backend in ("numpy", "ref", "interpret"):
        w = np.asarray(idx.overlap_matrix(backend))
        off = w[~np.eye(len(parts), dtype=bool)]
        assert (off == 0.0).all(), backend
    pi, pj = idx.candidate_pairs()
    assert len(pi) == 0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_permutation_invariance(seed):
    parts = _instance(seed)
    perm = np.random.default_rng(seed + 1).permutation(len(parts))
    idx = dp.PartitionIndex.from_partitions(parts)
    idx_p = dp.PartitionIndex.from_partitions([parts[p] for p in perm])
    w = idx.overlap_matrix("numpy")
    w_p = idx_p.overlap_matrix("numpy")
    assert np.allclose(w[np.ix_(perm, perm)], w_p, atol=1e-9)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_backend_differential(seed):
    """numpy / vmapped-jnp / pallas-interpret agree to 1e-5 (f32 kernels
    vs f64 host sweep)."""
    idx = dp.PartitionIndex.from_partitions(_instance(seed))
    w_np = idx.overlap_matrix("numpy")
    w_ref = np.asarray(idx.overlap_matrix("ref"))
    w_int = np.asarray(idx.overlap_matrix("interpret"))
    assert np.abs(w_np - w_ref).max() < 1e-5
    assert np.abs(w_np - w_int).max() < 1e-5


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_csr_round_trip_identity(seed):
    parts = _instance(seed)
    idx = dp.PartitionIndex.from_partitions(parts)
    back = idx.to_partitions()
    assert [(p.files, p.rho) for p in back] == \
           [(p.files, p.rho) for p in parts]
    # same FileSizes object -> memoized spans, read_cost bit-identical
    assert back[0].sizes is parts[0].sizes
    assert idx.read_cost() == pytest.approx(dp.read_cost(parts), abs=1e-9)
    for i in range(idx.n):
        row = idx.row(i)
        assert (np.diff(row) > 0).all()  # ascending, duplicate-free


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_candidate_pairs_exact(seed):
    """Unsampled candidate set == {(i, j) : overlap > 0, i < j}."""
    idx = dp.PartitionIndex.from_partitions(_instance(seed))
    w = idx.overlap_matrix("numpy")
    pi, pj = idx.candidate_pairs()
    got = set(zip(pi.tolist(), pj.tolist()))
    want = {(i, j) for i in range(idx.n) for j in range(i + 1, idx.n)
            if w[i, j] > 0.0}
    assert got == want


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_sampled_candidates_subset(seed):
    idx = dp.PartitionIndex.from_partitions(_instance(seed, n_parts=25))
    pi, pj = idx.candidate_pairs()
    exact = set(zip(pi.tolist(), pj.tolist()))
    si, sj = idx.candidate_pairs(sample=0.5, seed=seed)
    assert set(zip(si.tolist(), sj.tolist())) <= exact
    ci, cj = idx.candidate_pairs(max_degree=2)
    assert set(zip(ci.tolist(), cj.tolist())) <= exact


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_pair_overlap_spans_match_setwise(seed):
    parts = _instance(seed)
    idx = dp.PartitionIndex.from_partitions(parts)
    n = idx.n
    pi, pj = np.triu_indices(n, 1)
    inter = idx.pair_overlap_spans(pi, pj)
    for t in range(0, len(pi), 7):
        i, j = int(pi[t]), int(pj[t])
        assert inter[t] == pytest.approx(dp.overlap(parts[i], parts[j]),
                                         abs=1e-9)


def test_rectangular_block_matches_square():
    """The codes_b operand (the sharded row-block path) must reproduce the
    corresponding rows of the square sweep."""
    idx = dp.PartitionIndex.from_partitions(_instance(123, n_parts=12))
    codes, sizes, spans = idx.padded_codes()
    full = np.asarray(ops.fractional_overlap_matrix(codes, sizes, spans,
                                                    impl="ref"))
    blk = np.asarray(ops.fractional_overlap_matrix(
        codes[:5], sizes, spans[:5], codes_b=codes, spans_b=spans,
        impl="ref"))
    assert np.abs(full[:5] - blk).max() < 1e-6
    blk_i = np.asarray(ops.fractional_overlap_matrix(
        codes[:5], sizes, spans[:5], codes_b=codes, spans_b=spans,
        impl="interpret"))
    assert np.abs(full[:5] - blk_i).max() < 1e-5


def test_ops_dispatch_aliases():
    """'jnp' (the engine backend name) must resolve to the jnp oracle."""
    idx = dp.PartitionIndex.from_partitions(_instance(5, n_parts=6))
    codes, sizes, spans = idx.padded_codes()
    a = np.asarray(ops.fractional_overlap_matrix(codes, sizes, spans,
                                                 impl="jnp"))
    b = np.asarray(ops.fractional_overlap_matrix(codes, sizes, spans,
                                                 impl="ref"))
    assert np.array_equal(a, b)
