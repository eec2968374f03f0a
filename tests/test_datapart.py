"""DATAPART: G-PART invariants + ordered DP vs brute force (Thms 5/6)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import datapart as dp


def _parts_from_spec(spec, rhos):
    """spec: list of file-id tuples; files auto-sized 1.0 unless suffixed."""
    all_files = sorted({f for fs in spec for f in fs})
    sizes = dp.FileSizes({f: 1.0 for f in all_files})
    return [dp.Partition(frozenset(fs), r, sizes) for fs, r in zip(spec, rhos)]


def test_overlap_and_span():
    parts = _parts_from_spec([("a", "b", "c"), ("b", "c", "d")], [1, 1])
    assert parts[0].span == 3.0
    assert dp.overlap(parts[0], parts[1]) == 2.0
    assert dp.fractional_overlap(parts[0], parts[1]) == pytest.approx(0.5)


def test_gpart_merges_full_overlap():
    parts = _parts_from_spec([("a", "b"), ("a", "b"), ("x", "y")], [5, 5, 5])
    out = dp.g_part(parts, s_thresh=100.0)
    spans = sorted(p.span for p in out)
    assert len(out) == 2 and spans == [2.0, 2.0]


def test_gpart_respects_access_feasibility():
    # wildly different access rates must not merge
    parts = _parts_from_spec([("a", "b"), ("a", "b")], [1.0, 1e6])
    out = dp.g_part(parts, s_thresh=100.0, rho_c=4.0, rho_c_abs=10.0)
    assert len(out) == 2


def test_gpart_s_thresh_stops_growth():
    spec = [(f"f{i}", f"f{i+1}") for i in range(10)]
    parts = _parts_from_spec(spec, [1.0] * 10)
    out = dp.g_part(parts, s_thresh=3.0)
    # merged nodes exceeding s_thresh must not have kept merging: every
    # result is below s_thresh + one merge step's worth of files
    assert all(p.span <= 6.0 for p in out)


def test_gpart_covers_all_files():
    rng = np.random.default_rng(0)
    spec = [tuple(f"f{j}" for j in rng.choice(20, rng.integers(1, 6),
                                              replace=False))
            for _ in range(15)]
    parts = _parts_from_spec(spec, rng.uniform(1, 5, 15))
    out = dp.g_part(parts, s_thresh=8.0)
    orig = set().union(*[p.files for p in parts])
    got = set().union(*[p.files for p in out])
    assert got == orig


def test_gpart_reduces_duplication():
    rng = np.random.default_rng(1)
    # heavily overlapping families with comparable access rates
    spec = [tuple(f"f{j}" for j in range(i, i + 6)) for i in range(12)]
    parts = _parts_from_spec(spec, rng.uniform(2, 4, 12))
    merged = dp.g_part(parts, s_thresh=30.0)
    assert dp.duplication(merged) <= dp.duplication(parts)
    assert dp.read_cost(merged) >= 0


def _ordered_parts(rng, n):
    """Time-ordered partitions: window [i, i+w) of unit files."""
    files = {f"t{i}": float(rng.uniform(0.5, 2.0)) for i in range(n + 6)}
    sizes = dp.FileSizes(files)
    parts = []
    for i in range(n):
        w = int(rng.integers(2, 5))
        parts.append(dp.Partition(frozenset(f"t{j}" for j in range(i, i + w)),
                                  float(rng.uniform(0.5, 4.0)), sizes))
    return parts


def test_ordered_dp_matches_bruteforce():
    rng = np.random.default_rng(2)
    for _ in range(6):
        parts = _ordered_parts(rng, 6)
        no_merge_cost = dp.read_cost(parts)
        c_thresh = no_merge_cost * 1.5
        exact = dp.ordered_brute_force(parts, c_thresh)
        sol = dp.ordered_dp(parts, c_thresh, n_buckets=4000)
        assert exact is not None and sol is not None
        assert sol.cost <= c_thresh * 1.01
        # discretization may round cost up; space must match exact optimum
        assert sol.space == pytest.approx(exact.space, rel=2e-2)


def test_ordered_approx_bicriteria():
    """Thm 6: space <= OPT space, cost <= (1 + N*eps) * C."""
    rng = np.random.default_rng(3)
    parts = _ordered_parts(rng, 7)
    c = dp.read_cost(parts) * 1.2
    exact = dp.ordered_brute_force(parts, c)
    approx = dp.ordered_approx(parts, c, eps=1.0 / len(parts))
    assert exact is not None and approx is not None
    assert approx.space <= exact.space + 1e-9
    assert approx.cost <= 2.0 * c * 1.01   # (1,2) bi-criteria for eps=1/N


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_gpart_rho_conservation(seed):
    """Total access mass is conserved by merging."""
    rng = np.random.default_rng(seed)
    spec = [tuple(f"f{j}" for j in rng.choice(12, rng.integers(1, 5),
                                              replace=False))
            for _ in range(8)]
    rhos = rng.uniform(0.5, 8.0, 8)
    parts = _parts_from_spec(spec, rhos)
    out = dp.g_part(parts, s_thresh=rng.uniform(2, 20))
    assert sum(p.rho for p in out) == pytest.approx(sum(rhos))


def test_merge_all_baseline():
    parts = _parts_from_spec([("a", "b"), ("b", "c")], [1, 2])
    allm = dp.merge_all(parts)
    assert len(allm) == 1 and allm[0].span == 3.0 and allm[0].rho == 3.0


# --------------------------------------------- array-native core equivalence
def _random_instance(seed, n_parts=20, n_files=40, unit=False):
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


def _canon(parts):
    return sorted((tuple(sorted(p.files)), round(p.rho, 9)) for p in parts)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_gpart_equals_ref(seed):
    """The exact-equivalence pin: array-native g_part returns the SAME
    partitions and read_cost as the original pair-by-pair g_part_ref."""
    for unit in (True, False):
        parts = _random_instance(seed, unit=unit)
        med = float(np.median([p.span for p in parts]))
        for mult in (1.5, 3.0, 10.0):
            ref = dp.g_part_ref(parts, s_thresh=mult * med)
            arr = dp.g_part(parts, s_thresh=mult * med, backend="numpy")
            assert _canon(ref) == _canon(arr)
            assert dp.read_cost(arr) == pytest.approx(dp.read_cost(ref),
                                                      abs=1e-12)


def test_gpart_equals_ref_device_backends():
    """Candidate graphs from the jnp / pallas-interpret overlap matrix give
    the same merge result (weights are recomputed in f64 either way)."""
    parts = _random_instance(77)
    med = float(np.median([p.span for p in parts]))
    ref = dp.g_part_ref(parts, s_thresh=3.0 * med)
    for backend in ("ref", "interpret"):
        arr = dp.g_part(parts, s_thresh=3.0 * med, backend=backend)
        assert _canon(ref) == _canon(arr)


def test_gpart_sampled_read_cost_close():
    """MinHash-style sampling: fewer candidate edges, read_cost within
    1.1x of the exact merge on a moderate instance."""
    parts = _random_instance(5, n_parts=120, n_files=150)
    med = float(np.median([p.span for p in parts]))
    exact = dp.read_cost(dp.g_part(parts, s_thresh=3.0 * med))
    sampled = dp.read_cost(dp.g_part(parts, s_thresh=3.0 * med,
                                     sample=0.6, sample_seed=0))
    assert sampled <= exact * 1.1
    # rho conservation holds regardless of which edges were sampled
    tot = sum(p.rho for p in parts)
    out = dp.g_part(parts, s_thresh=3.0 * med, sample=0.3, sample_seed=1)
    assert sum(p.rho for p in out) == pytest.approx(tot)


def test_filesizes_span_memoized_and_matches_index():
    """Satellite regression: memoized FileSizes.span agrees with the
    vectorized index path to 1e-9, and repeat lookups hit the cache."""
    parts = _random_instance(11)
    fs = parts[0].sizes
    idx = dp.PartitionIndex.from_partitions(parts)
    spans = idx.span()
    for i, p in enumerate(parts):
        assert fs.span(p.files) == pytest.approx(spans[i], abs=1e-9)
    assert len(fs._span_cache) >= len({p.files for p in parts})
    cached = fs.span(parts[0].files)
    assert fs._span_cache[parts[0].files] == cached  # second hit, same value


def test_index_vectorized_metrics_agree():
    parts = _random_instance(13)
    idx = dp.PartitionIndex.from_partitions(parts)
    assert idx.read_cost() == pytest.approx(dp.read_cost(parts), abs=1e-9)
    assert idx.duplication() == pytest.approx(dp.duplication(parts),
                                              abs=1e-12)
    assert idx.fractional_overlap(0, 1) == pytest.approx(
        dp.fractional_overlap(parts[0], parts[1]), abs=1e-12)
