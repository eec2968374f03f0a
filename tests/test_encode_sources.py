"""Per-source dictionary encoding (``encode_dtype_classes`` with
``sources``) against the encoding it replaced, which concatenated every
partition's renderings of a class and sorted them at once.

The oracle below is that body, kept as the reference: the per-source path
must give all nine ``ClassCodes`` arrays bit for bit, render each covered
source row once per column, and report what it rendered on its span.
"""

import contextlib
import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tracing
from repro.core.compredict import CompressionPredictor, query_samples
from repro.core.costs import azure_table
from repro.core.engine import PlacementEngine, ScopeConfig
from repro.data import tpch
from repro.data.tables import (DTYPE_CLASSES, ClassCodes, Table, dtype_class,
                               encode_dtype_classes)
from repro.storage.codecs import available_schemes, codec_by_name


def _oracle(tables: Sequence[Table]) -> Dict[str, ClassCodes]:
    """Every partition's values of a class rendered, concatenated and
    uniqued in one array; localized by a per-partition ``np.unique``."""
    out: Dict[str, ClassCodes] = {}
    N = len(tables)
    for d in DTYPE_CLASSES:
        flats: List[np.ndarray] = []
        n_rows = np.zeros(N, np.int32)
        n_cols = np.zeros(N, np.int32)
        for i, t in enumerate(tables):
            cols = [t._col_str(v) for v in t.columns.values()
                    if dtype_class(v) == d]
            n_rows[i] = t.num_rows
            n_cols[i] = len(cols)
            flats.append(np.stack(cols, axis=1).reshape(-1) if cols
                         else np.empty(0, "<U1"))
        n_valid = np.array([f.shape[0] for f in flats], np.int32)
        total = int(n_valid.sum())
        if total:
            uniq, inv = np.unique(np.concatenate(flats), return_inverse=True)
            global_lengths = np.char.str_len(
                uniq.astype(str)).astype(np.float32)
        else:
            inv = np.zeros(0, np.int64)
            global_lengths = np.zeros(1, np.float32)
        M = max(int(n_valid.max()) if N else 0, 1)
        global_codes = np.full((N, M), -1, np.int32)
        locals_: List[Tuple[np.ndarray, np.ndarray]] = []
        off = 0
        for i, nv in enumerate(n_valid):
            g = inv[off:off + nv]
            global_codes[i, :nv] = g
            locals_.append(np.unique(g, return_inverse=True))
            off += nv
        n_distinct = np.array([len(lu) for lu, _ in locals_], np.int32)
        Vmax = max(int(n_distinct.max()) if N else 0, 1)
        codes = np.full((N, M), -1, np.int32)
        vocab = np.full((N, Vmax), -1, np.int32)
        lengths = np.zeros((N, Vmax), np.float32)
        for i, (lu, linv) in enumerate(locals_):
            codes[i, :n_valid[i]] = linv
            vocab[i, :len(lu)] = lu
            lengths[i, :len(lu)] = global_lengths[lu]
        out[d] = ClassCodes(codes=codes, n_valid=n_valid, n_rows=n_rows,
                            n_cols=n_cols, lengths=lengths, vocab=vocab,
                            n_distinct=n_distinct, global_codes=global_codes,
                            global_lengths=global_lengths)
    return out


def _assert_identical(got: Dict[str, ClassCodes],
                      want: Dict[str, ClassCodes]) -> None:
    assert set(got) == set(want) == set(DTYPE_CLASSES)
    for d in DTYPE_CLASSES:
        for f in dataclasses.fields(ClassCodes):
            a, b = getattr(got[d], f.name), getattr(want[d], f.name)
            assert a.dtype == b.dtype, (d, f.name)
            assert np.array_equal(a, b), (d, f.name)


def _source(seed: int, n: int, *, ints=1, floats=1, strs=1) -> Table:
    rng = np.random.default_rng(seed)
    cols = {}
    for c in range(ints):
        cols[f"i{c}"] = rng.integers(-50, 400, n)
    for c in range(floats):
        cols[f"f{c}"] = rng.normal(scale=30.0, size=n)
    for c in range(strs):
        width = 3 + 40 * c               # a short and a long column
        cols[f"s{c}"] = np.array(["".join(rng.choice(list("abcxyz "), k))
                                  for k in rng.integers(1, width, n)])
    return Table(f"src{seed}", cols)


def _parts(src: Table, *ranges) -> List[Tuple[Table, np.ndarray]]:
    return [(src, np.arange(lo, hi)) for lo, hi in ranges]


def _overlapping():
    src = _source(1, 120, strs=2)
    return _parts(src, (0, 60), (30, 100), (0, 120), (90, 120))


def _two_sources():
    a, b = _source(2, 80, ints=2), _source(3, 50, floats=2, strs=2)
    return _parts(a, (0, 40), (20, 80)) + _parts(b, (10, 50)) + \
        _parts(a, (70, 80))


def _class_missing():
    ints_strs = _source(4, 60, floats=0)
    floats = _source(5, 40, ints=0, strs=0)
    return _parts(ints_strs, (0, 30), (20, 60)) + _parts(floats, (0, 40))


def _same_rendering():
    # distinct floats whose "%.4f" renderings collide
    f = np.array([1.00001, 1.00002, 1.00004, 2.5, 2.50001, -0.00001, 0.0])
    src = Table("floats", {"f0": f, "f1": f[::-1].copy()})
    return _parts(src, (0, 4), (2, 7))


def _same_string_two_columns():
    words = np.array(["red", "green", "blue", "red", "teal", "green"])
    src = Table("words", {"a": words, "b": np.roll(words, 2),
                          "n": np.arange(6)})
    return _parts(src, (0, 3), (1, 6))


def _one_row():
    src = _source(6, 30, strs=2)
    return _parts(src, (7, 8), (0, 30), (29, 30))


CASES = {"overlapping partitions of one source": _overlapping,
         "partitions of two sources": _two_sources,
         "a partition with no column of a class": _class_missing,
         "floats with the same rendering": _same_rendering,
         "one string in two columns of a class": _same_string_two_columns,
         "one-row partitions": _one_row}


@pytest.mark.parametrize("case", sorted(CASES))
def test_per_source_encoding_matches_the_oracle(case):
    sources = CASES[case]()
    tables = [src.select(rows) for src, rows in sources]
    _assert_identical(encode_dtype_classes(tables, sources),
                      _oracle(tables))


@pytest.mark.parametrize("case", sorted(CASES))
def test_encoding_without_sources_matches_the_oracle(case):
    tables = [src.select(rows) for src, rows in CASES[case]()]
    _assert_identical(encode_dtype_classes(tables), _oracle(tables))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_random_partitions_of_random_sources_match_the_oracle(seed):
    rng = np.random.default_rng(seed)
    srcs = [_source(seed + s, int(rng.integers(1, 90)),
                    ints=int(rng.integers(0, 3)),
                    floats=int(rng.integers(0, 3)),
                    strs=int(rng.integers(1, 3)))
            for s in range(int(rng.integers(1, 4)))]
    sources = []
    for _ in range(int(rng.integers(1, 9))):
        src = srcs[int(rng.integers(len(srcs)))]
        k = int(rng.integers(1, src.num_rows + 1))
        sources.append((src, np.sort(rng.choice(src.num_rows, k,
                                                replace=False))))
    tables = [src.select(rows) for src, rows in sources]
    _assert_identical(encode_dtype_classes(tables, sources),
                      _oracle(tables))


@pytest.fixture
def rendered(monkeypatch):
    """Values ``Table._col_str`` renders, and each span opened with its
    arguments, while the test runs."""
    seen = {"values": 0, "spans": []}
    real = Table._col_str

    def count(self, v):
        seen["values"] += len(v)
        return real(self, v)

    @contextlib.contextmanager
    def record(name, **args):
        seen["spans"].append((name, args))
        yield

    monkeypatch.setattr(Table, "_col_str", count)
    monkeypatch.setattr(tracing, "span", record)
    return seen


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_covered_source_row_is_rendered_once(rendered, case):
    sources = CASES[case]()
    tables = [src.select(rows) for src, rows in sources]
    covered = {}
    for src, rows in sources:
        covered.setdefault(id(src), (src, set()))[1].update(rows.tolist())
    expect = sum(len(rows) * len(src.columns)
                 for src, rows in covered.values())
    holds = sum(t.num_rows * len(t.columns) for t in tables)
    encode_dtype_classes(tables, sources)
    assert rendered["values"] == expect
    assert rendered["spans"] == [("features.encode.render",
                                  {"values": holds, "rendered": expect})]


# ------------------------------------------------------- through the engine
class _Kept(CompressionPredictor):
    """Keeps the arguments and the result of CompressStage's call."""

    def features(self, tables, layout, **kw):
        self.call = (tables, layout, kw)
        self.X = super().features(tables, layout, **kw)
        return self.X


def test_lake_plan_features_identical_with_and_without_provenance():
    db = tpch.generate(scale_rows=800, seed=11)
    qs = tpch.generate_queries(db, n_per_template=2, seed=12)
    parts, file_rows = tpch.partitions_from_queries(db, qs)
    scheme = available_schemes(("zstd-3", "zlib-6", "zlib-1"))[0]
    pred = _Kept(model_name="SVR").fit(
        query_samples(qs, db.tables, max_rows=200)[:16], layouts=("col",),
        codecs=[codec_by_name(scheme)])
    cfg = ScopeConfig(schemes=("none", scheme), predictor=pred,
                      partition_backend="jnp", feature_backend="jnp")
    PlacementEngine(azure_table(), cfg).run(parts, file_rows)
    tables, layout, kw = pred.call
    sources = kw["sources"]
    assert sources is not None and len(sources) == len(tables) > 1
    assert len({id(src) for src, _ in sources}) < len(sources)
    for t, (src, rows) in zip(tables, sources):
        assert all(np.array_equal(t.columns[c], src.columns[c][rows])
                   for c in src.columns)
    plain = dict(kw, sources=None)
    X = CompressionPredictor.features(pred, tables, layout, **plain)
    assert np.array_equal(pred.X, X)
    _assert_identical(encode_dtype_classes(tables, sources), _oracle(tables))
