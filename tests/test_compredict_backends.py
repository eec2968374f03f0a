"""Differential + property suite for the COMPREDICT feature backends.

The batched device pipeline (jnp / Pallas-interpret) is a rewrite of a
numeric hot path, so it is pinned three ways against the NumPy loop:

* differential — all three backends agree to 1e-5 across dtype mixes,
  ragged partition lengths, n < block, pad boundaries, empty dtype
  classes, and single-value (zero-entropy) payloads;
* properties — row-permutation invariance, histogram additivity under
  partition concatenation, the log(k) entropy upper bound, and
  backend-choice invariance of ``predict_matrix``;
* regression — integer bucket edges cover every row exactly once.
"""

import functools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compredict import (CompressionPredictor, _bucket_edges,
                                   bucketed_weighted_entropy,
                                   extract_features, extract_features_batch,
                                   query_samples, weighted_entropy)
from repro.data import tpch
from repro.data.tables import DTYPE_CLASSES, Table, encode_dtype_classes
from repro.kernels import ops
from repro.kernels.entropy_features import (_histogram_index,
                                            weighted_entropy_features,
                                            weighted_entropy_features_ref)

TOL = dict(rtol=1e-5, atol=1e-5)
STRS = np.array(["alpha", "beta", "gamma", "delta", "epsilon", "zz"])


def _mk_table(n_rows: int, seed: int, *, n_int=1, n_float=1, n_str=1,
              vocab: int = 6, constant: bool = False) -> Table:
    rng = np.random.default_rng(seed)
    cols = {}
    for c in range(n_int):
        cols[f"i{c}"] = (np.full(n_rows, 7) if constant
                         else rng.integers(0, vocab * 37, n_rows))
    for c in range(n_float):
        cols[f"f{c}"] = (np.full(n_rows, 1.5) if constant
                         else rng.normal(size=n_rows).round(2))
    for c in range(n_str):
        cols[f"s{c}"] = (np.full(n_rows, "aaa") if constant
                         else rng.choice(STRS[:vocab], n_rows))
    return Table(f"t{seed}", cols)


# ------------------------------------------------------------- differential
@pytest.mark.parametrize("kind", ["weighted_entropy", "bucketed"])
@pytest.mark.parametrize("mix", [
    dict(n_int=2, n_float=1, n_str=1),      # full dtype mix
    dict(n_int=0, n_float=0, n_str=3),      # int/float classes empty
    dict(n_int=3, n_float=0, n_str=0),      # only ints
    dict(n_int=0, n_float=2, n_str=0),      # only floats
])
def test_backends_agree_across_dtype_mixes(kind, mix):
    """numpy vs jnp vs Pallas(interpret) on ragged batches, to 1e-5."""
    tabs = [_mk_table(n, 10 + n, **mix) for n in (7, 64, 129, 200, 1)]
    X_np = extract_features_batch(tabs, "col", kind, "numpy")
    X_jnp = extract_features_batch(tabs, "col", kind, "jnp")
    X_pal = extract_features_batch(tabs, "col", kind, "interpret")
    np.testing.assert_allclose(X_jnp, X_np, **TOL)
    np.testing.assert_allclose(X_pal, X_np, **TOL)


def test_backends_agree_on_tpch_query_samples():
    """Real mixed-schema partitions (query results over TPC-H tables)."""
    db = tpch.generate(scale_rows=600, seed=3)
    qs = tpch.generate_queries(db, n_per_template=2, seed=4)
    tabs = query_samples(qs, db.tables, max_rows=300)[:6]
    for kind in ("weighted_entropy", "bucketed"):
        X_np = extract_features_batch(tabs, "row", kind, "numpy")
        X_jnp = extract_features_batch(tabs, "row", kind, "jnp")
        X_pal = extract_features_batch(tabs, "row", kind, "interpret")
        np.testing.assert_allclose(X_jnp, X_np, **TOL)
        np.testing.assert_allclose(X_pal, X_np, **TOL)


@pytest.mark.parametrize("n,block", [
    (37, 128),      # vocabulary in three lane tiles, the last one padded
    (128, 256),     # two tiles, the second mostly padding
    (130, 384),     # one tile covers the whole padded vocabulary
    (1, 100),       # single value; block rounds up to one 128-lane tile
])
def test_kernel_vs_ref_pad_boundaries(n, block):
    """Pallas grid kernel (interpret) vs the vmapped-jnp oracle at ragged
    lengths, with vocabulary tiles straddling the padded width; pads must
    never leak."""
    rng = np.random.default_rng(n)
    N, V, nb = 3, 300, 5
    n_cols = np.array([2, 1, 3], np.int32)
    n_valid = np.minimum(n, np.array([n, max(n - 5, 1), n], np.int32))
    n_valid = (n_valid // n_cols) * n_cols          # whole rows
    n_valid = np.maximum(n_valid, n_cols)
    n_rows = n_valid // n_cols
    M = int(n_valid.max())
    codes = np.full((N, M), -1, np.int32)
    for i in range(N):
        codes[i, :n_valid[i]] = rng.integers(0, V, n_valid[i])
    lengths = rng.integers(1, 9, V).astype(np.float32)
    s_ref, b_ref = weighted_entropy_features_ref(
        codes, n_valid, n_rows, n_cols, lengths, n_buckets=nb)
    s_pal, b_pal = weighted_entropy_features(
        codes, n_valid, n_rows, n_cols, lengths, n_buckets=nb, block=block,
        interpret=True)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref), **TOL)
    np.testing.assert_allclose(np.asarray(b_pal), np.asarray(b_ref), **TOL)


def test_histogram_index_does_not_wrap_past_int32():
    """95 partitions x 5 buckets over the 4.56M-entry SF 1 float vocabulary
    put N * nb * vocab past 2**31: a flattened int32 key would wrap to a
    negative index and count into the wrong cells. The kernel's 2-D
    (row, col) index keeps both axes in range."""
    N, nb, M, V = 95, 5, 10, 4_561_471
    n_valid = np.full(N, 8, np.int32)
    n_cols = np.full(N, 2, np.int32)
    n_rows = n_valid // n_cols
    codes = np.full((N, M), -1, np.int32)
    codes[:, :8] = V - 1 - np.arange(8, dtype=np.int32)
    assert N * nb * V >= 2**31
    row, col = (np.asarray(a) for a in _histogram_index(
        codes, n_valid, n_rows, n_cols, nb))
    # 4 rows of 2 values; bucket b holds rows [floor(b*4/5), floor((b+1)*4/5))
    bucket = np.array([1, 1, 2, 2, 3, 3, 4, 4])
    np.testing.assert_array_equal(
        row[:, :8], np.arange(N)[:, None] * nb + bucket[None, :])
    np.testing.assert_array_equal(row[:, 8:], N * nb)    # pads are dropped
    np.testing.assert_array_equal(col[:, :8], codes[:, :8])
    assert row.min() >= 0 and col.min() >= 0 and col.max() < V


def test_ops_dispatch_ref_equals_interpret():
    codes = np.array([[0, 1, 1, 2, -1, -1]], np.int32)
    args = (codes, np.array([4]), np.array([2]), np.array([2]),
            np.array([3.0, 1.0, 2.0], np.float32))
    s_a, b_a = ops.weighted_entropy_features(*args, n_buckets=2, impl="ref")
    s_b, b_b = ops.weighted_entropy_features(*args, n_buckets=2,
                                             impl="interpret")
    np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_b), **TOL)
    np.testing.assert_allclose(np.asarray(b_a), np.asarray(b_b), **TOL)


def test_single_value_payload_is_zero_entropy():
    """Constant columns carry exactly 0 nats in every backend and bucket."""
    tabs = [_mk_table(50, 1, constant=True), _mk_table(3, 2, constant=True)]
    for backend in ("numpy", "jnp", "interpret"):
        X = extract_features_batch(tabs, "col", "bucketed", backend)
        base, blk = 3, 5
        for ci in range(len(DTYPE_CLASSES)):
            assert np.allclose(X[:, base + ci * blk], 0.0, atol=1e-6), backend
            assert np.allclose(X[:, base + ci * blk + 1], 0.0, atol=1e-6)
        np.testing.assert_allclose(X[:, 18:], 0.0, atol=1e-6)


def test_all_backends_handle_zero_rows():
    """0-row partitions (windows can empty out mid-stream) come back as
    all-zero entropy features in EVERY backend — the NumPy loop used to
    divide by zero here, breaking backend invariance."""
    tabs = [_mk_table(0, 5), _mk_table(10, 6)]
    outs = {}
    for backend in ("numpy", "jnp", "interpret"):
        X = extract_features_batch(tabs, "col", "weighted_entropy", backend)
        assert np.isfinite(X).all(), backend
        np.testing.assert_allclose(X[0, 3:], [0, 0, 0, 0, 1] * 3, atol=1e-6)
        outs[backend] = X
    np.testing.assert_allclose(outs["jnp"], outs["numpy"], **TOL)
    np.testing.assert_allclose(outs["interpret"], outs["numpy"], **TOL)


def test_n_buckets_is_honored_by_every_backend():
    """Width and values must not depend on the backend when n_buckets != 5,
    and the empty-batch width formula must match the non-empty one."""
    tabs = [_mk_table(17, 8), _mk_table(40, 9)]
    outs = {b: extract_features_batch(tabs, "col", "bucketed", b, n_buckets=3)
            for b in ("numpy", "jnp", "interpret")}
    for b, X in outs.items():
        assert X.shape == (2, 18 + 3 * 3), b
    np.testing.assert_allclose(outs["jnp"], outs["numpy"], **TOL)
    np.testing.assert_allclose(outs["interpret"], outs["numpy"], **TOL)
    empty = extract_features_batch([], "col", "bucketed", "numpy",
                                   n_buckets=3)
    assert empty.shape == (0, outs["numpy"].shape[1])


# --------------------------------------------------------------- properties
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_row_permutation_invariance(seed):
    """Weighted entropy is a bag statistic: shuffling rows changes nothing
    (numpy dict and the batched jnp backend alike)."""
    t = _mk_table(40 + seed % 60, seed, n_int=2)
    perm = np.random.default_rng(seed).permutation(t.num_rows)
    tp = t.select(perm)
    assert weighted_entropy(t) == pytest.approx(weighted_entropy(tp))
    X = extract_features_batch([t, tp], "col", "weighted_entropy", "jnp")
    np.testing.assert_allclose(X[0], X[1], **TOL)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_histogram_additivity_under_concat(seed):
    """Shared-vocabulary histograms add under partition concatenation —
    the invariant that makes incremental/merged feature maintenance sound."""
    t1 = _mk_table(30 + seed % 20, seed)
    t2 = _mk_table(45 + seed % 11, seed + 1)
    enc = encode_dtype_classes([t1, t2, t1.concat(t2)])
    for d in DTYPE_CLASSES:
        cc = enc[d]
        V = cc.vocab_size
        h = [np.bincount(cc.global_codes[i, :cc.n_valid[i]], minlength=V)
             for i in range(3)]
        np.testing.assert_array_equal(h[0] + h[1], h[2])


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 200))
def test_entropy_upper_bound_log_k(k):
    """A k-symbol payload has plain entropy <= log(k) and weighted entropy
    <= maxlen * log(k), with equality for the uniform payload."""
    vals = np.array([f"s{i:03d}" for i in range(k)])
    t = Table("k", {"s": np.tile(vals, 4)})
    enc = encode_dtype_classes([t])["str"]
    summary, _ = ops.weighted_entropy_features(
        enc.codes, enc.n_valid, enc.n_rows, enc.n_cols, enc.lengths,
        impl="ref")
    H_w, H_plain = float(summary[0, 0]), float(summary[0, 1])
    assert H_plain <= np.log(k) * (1 + 1e-5) + 1e-6
    assert H_plain == pytest.approx(np.log(k), rel=1e-4)   # uniform payload
    assert H_w <= 4 * np.log(k) * (1 + 1e-5) + 1e-6        # len("sNNN") = 4


@functools.lru_cache(maxsize=1)
def _fitted_predictor():
    from repro.storage.codecs import available_schemes, codec_by_name
    db = tpch.generate(scale_rows=500, seed=7)
    qs = tpch.generate_queries(db, n_per_template=3, seed=8)
    samples = query_samples(qs, db.tables, max_rows=250)[:40]
    scheme = available_schemes(("zstd-3", "zlib-6", "zlib-1"))[0]
    pred = CompressionPredictor(model_name="SVR").fit(
        samples, layouts=("col",), codecs=[codec_by_name(scheme)])
    tabs = [db.tables["orders"].head(n) for n in (33, 90, 150)]
    return pred, scheme, tabs


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 3))
def test_predict_matrix_backend_invariance(seed):
    """The backend is an implementation detail: predictions through the
    same fitted models must not depend on it."""
    pred, scheme, tabs = _fitted_predictor()
    subset = tabs[seed % len(tabs):]
    out = {b: pred.predict_matrix(subset, ["none", scheme], "col",
                                  feature_backend=b)
           for b in ("numpy", "jnp", "interpret")}
    for b in ("jnp", "interpret"):
        np.testing.assert_allclose(out[b][0], out["numpy"][0], rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(out[b][1], out["numpy"][1], rtol=1e-4,
                                   atol=1e-8)
    assert (out["numpy"][0][:, 0] == 1.0).all()     # scheme 'none' pinned
    assert (out["numpy"][1][:, 0] == 0.0).all()


# --------------------------------------------------------------- regression
@pytest.mark.parametrize("n", [0, 1, 2, 4, 5, 7, 9, 10, 101, 9998])
@pytest.mark.parametrize("nb", [1, 3, 5])
def test_bucket_edges_cover_every_row_exactly_once(n, nb):
    """The final row must never fall off the last bucket when
    n % n_buckets != 0: integer edges partition range(n) exactly."""
    edges = _bucket_edges(n, nb)
    assert edges[0] == 0 and edges[-1] == n
    assert (np.diff(edges) >= 0).all()
    covered = np.concatenate([np.arange(lo, hi)
                              for lo, hi in zip(edges[:-1], edges[1:])])
    np.testing.assert_array_equal(covered, np.arange(n))


def test_bucketed_entropy_sees_the_final_row():
    """n=7, nb=5: a distinctive final row must land in the last bucket —
    a truncated last edge would report 0 entropy there."""
    vals = np.array(["a"] * 6 + ["unique-tail"])
    t = Table("tail", {"s": vals})
    feats = bucketed_weighted_entropy(t, n_buckets=5)
    str_idx = DTYPE_CLASSES.index("str")
    last_bucket = feats[4 * len(DTYPE_CLASSES) + str_idx]
    assert last_bucket > 0.0                      # {'a', 'unique-tail'} mix
    # and the device backends agree on the same tail bucket
    X_np = extract_features_batch([t], "col", "bucketed", "numpy")
    X_jnp = extract_features_batch([t], "col", "bucketed", "jnp")
    np.testing.assert_allclose(X_jnp, X_np, **TOL)


def test_batch_matches_single_extract_and_sizes_passthrough():
    tabs = [_mk_table(n, n) for n in (12, 33)]
    sizes = [t.nbytes("row") for t in tabs]
    X = extract_features_batch(tabs, "row", "bucketed", "numpy", sizes=sizes)
    for i, t in enumerate(tabs):
        np.testing.assert_array_equal(
            X[i], extract_features(t, "row", "bucketed", size=sizes[i]))
    with pytest.raises(ValueError):
        extract_features_batch(tabs, "row", "bucketed", "tpu")
