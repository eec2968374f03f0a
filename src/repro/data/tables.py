"""Columnar table abstraction + row/column serialization.

Stands in for parquet (column-major) vs CSV (row-major) in COMPREDICT's
layout study (§V "Row vs Column Oriented Storage"). A table is a dict of
named NumPy columns with dtype classes {int, float, str}.

:func:`encode_dtype_classes` additionally provides the device-transfer view
used by the batched COMPREDICT feature backends: per dtype class, every
source column's covered rows rendered once to strings, dictionary-encoded
against a vocabulary shared by all partitions, and laid out as padded
int32 code matrices that :mod:`repro.kernels.entropy_features` can
histogram in one dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import tracing

DTYPE_CLASSES = ("int", "float", "str")


def dtype_class(col: np.ndarray) -> str:
    if col.dtype.kind in "iu":
        return "int"
    if col.dtype.kind == "f":
        return "float"
    return "str"


@dataclasses.dataclass
class Table:
    name: str
    columns: Dict[str, np.ndarray]

    @property
    def num_rows(self) -> int:
        return len(next(iter(self.columns.values()))) if self.columns else 0

    def select(self, mask_or_idx) -> "Table":
        return Table(self.name, {k: v[mask_or_idx] for k, v in self.columns.items()})

    def head(self, n: int) -> "Table":
        return self.select(slice(0, n))

    def concat(self, other: "Table") -> "Table":
        return Table(self.name, {k: np.concatenate([v, other.columns[k]])
                                 for k, v in self.columns.items()})

    def sort_by(self, col: str) -> "Table":
        return self.select(np.argsort(self.columns[col], kind="stable"))

    # -------------------------------------------------------- serialization
    def _str_cols(self) -> List[np.ndarray]:
        out = []
        for v in self.columns.values():
            if dtype_class(v) == "float":
                out.append(np.char.mod("%.4f", v))
            elif dtype_class(v) == "int":
                out.append(np.char.mod("%d", v))
            else:
                out.append(v.astype(str))
        return out

    def to_row_bytes(self) -> bytes:
        """CSV-like row-major layout: rows of comma-joined fields."""
        cols = self._str_cols()
        if not cols:
            return b""
        joined = cols[0]
        for c in cols[1:]:
            joined = np.char.add(np.char.add(joined, ","), c)
        return ("\n".join(joined.tolist()) + "\n").encode()

    def to_col_bytes(self) -> bytes:
        """Parquet-like column-major layout: each column contiguous."""
        chunks = []
        for name, v in self.columns.items():
            header = f"#{name}\n".encode()
            body = ("\n".join(np.asarray(self._col_str(v)).tolist()) + "\n").encode()
            chunks.append(header + body)
        return b"".join(chunks)

    def _col_str(self, v: np.ndarray) -> np.ndarray:
        if dtype_class(v) == "float":
            return np.char.mod("%.4f", v)
        if dtype_class(v) == "int":
            return np.char.mod("%d", v)
        return v.astype(str)

    def serialize(self, layout: str) -> bytes:
        if layout == "row":
            return self.to_row_bytes()
        if layout == "col":
            return self.to_col_bytes()
        raise ValueError(layout)

    # ---------------------------------------------------------------- sizes
    def nbytes(self, layout: str = "row") -> int:
        return len(self.serialize(layout))


# --------------------------------------------------- device-transfer views
#: Where one partition's rows come from: its source table and the row
#: indices it holds, so that ``source.select(rows)`` is the partition.
Source = Tuple[Table, np.ndarray]


@dataclasses.dataclass
class ClassCodes:
    """Integer view of one dtype class across N partitions, device-ready.

    Values are the string renderings (``Table._col_str``) of every column of
    the class, dictionary-encoded against a vocabulary shared by all N
    partitions (``global_codes`` / ``global_lengths`` — histograms over
    these are additive under partition concatenation), then *localized*:
    ``codes`` index each partition's own compact vocabulary so histogram
    width scales with per-partition distinct counts, not the dataset-wide
    cardinality (high-precision float columns would otherwise blow the
    vocabulary into the 1e5 range). Within a partition the layout is
    row-major (position ``r * n_cols + c``), which makes the bucketed
    20%-of-rows entropy a histogram over contiguous code ranges.

    The shared vocabulary is the sorted set of every rendering the N
    partitions hold. :func:`encode_dtype_classes` builds it from each
    source column's own vocabulary, so it does not depend on which
    partitions share a source or its rows.
    """

    codes: np.ndarray          # (N, M)    int32 local codes, -1 padded
    n_valid: np.ndarray        # (N,)      int32, values per partition
    n_rows: np.ndarray         # (N,)      int32, rows per partition
    n_cols: np.ndarray         # (N,)      int32, columns of this class
    lengths: np.ndarray        # (N, Vmax) float32, len(s) per local slot
    vocab: np.ndarray          # (N, Vmax) int32, global code per local slot
    n_distinct: np.ndarray     # (N,)      int32, live local slots
    global_codes: np.ndarray   # (N, M)    int32 shared-vocab codes, -1 pad
    global_lengths: np.ndarray  # (V,)     float32, len(s) per global entry

    @property
    def vocab_size(self) -> int:
        return int(self.global_lengths.shape[0])


def encode_dtype_classes(tables: Sequence["Table"],
                         sources: Optional[Sequence[Source]] = None,
                         ) -> Dict[str, ClassCodes]:
    """One-pass dictionary encoding of N partitions for the feature kernels.

    Returns ``{dtype_class: ClassCodes}``. This is COMPREDICT's "one-time
    full scan" (paper §V): strings are rendered and uniqued once here (the
    NumPy feature path re-renders every column per bucket); localization
    and every subsequent feature extraction — including per-batch
    re-prediction on the streaming hot path — are pure integer work (see
    ``repro.core.compredict.extract_features_batch``).

    ``sources[i]`` is partition i's ``(source table, row indices)``, with
    ``tables[i] == source.select(rows)``; without it each table is its own
    source holding all of its rows. Encoding works per source column:

    * each source's covered rows (the union of its partitions' rows) are
      rendered once per column and uniqued at the column's own width, so
      a row that several partitions hold is rendered once, and a short
      column is never padded to the width of a long one;
    * the columns' vocabularies merge into the class's sorted global
      vocabulary (the set of every rendering the partitions hold);
    * each partition's row-major global codes are integer gathers through
      its rows' positions among the covered rows, and its local codes a
      presence mask over the global vocabulary and a rank gather.

    The span ``features.encode.render`` covers the rendering, with
    ``values`` (values the partitions hold) and ``rendered`` (source
    values rendered) as its arguments.
    """
    if sources is None:
        sources = [(t, np.arange(t.num_rows)) for t in tables]
    N = len(tables)
    # each distinct source table once, with the rows its partitions cover
    by_src: Dict[int, Tuple[Table, List[np.ndarray]]] = {}
    for src, rows in sources:
        by_src.setdefault(id(src), (src, []))[1].append(rows)
    covered = {k: (src, np.unique(np.concatenate(rows)))
               for k, (src, rows) in by_src.items()}
    pos = [np.searchsorted(covered[id(src)][1], rows)
           for src, rows in sources]

    values = sum(len(rows) * len(src.columns) for src, rows in sources)
    rendered = sum(len(rows) * len(src.columns)
                   for src, rows in covered.values())
    # (class, source) -> [(column vocabulary, code of each covered row)]
    with tracing.span("features.encode.render", values=values,
                      rendered=rendered):
        dicts = {(d, k): [np.unique(src._col_str(v[rows]),
                                    return_inverse=True)
                          for v in src.columns.values()
                          if dtype_class(v) == d]
                 for k, (src, rows) in covered.items()
                 for d in DTYPE_CLASSES}

    n_rows = np.array([t.num_rows for t in tables], np.int32)
    out: Dict[str, ClassCodes] = {}
    for d in DTYPE_CLASSES:
        n_cols = np.array([len(dicts[d, id(src)]) for src, _ in sources],
                          np.int32)
        n_valid = n_rows * n_cols
        if int(n_valid.sum()):
            uniq, remap = np.unique(
                np.concatenate([u for k in covered for u, _ in dicts[d, k]]),
                return_inverse=True)
            global_lengths = np.char.str_len(
                uniq.astype(str)).astype(np.float32)
        else:
            remap = np.zeros(0, np.int64)
            global_lengths = np.zeros(1, np.float32)
        # (covered rows, columns) global codes of each source
        grid, off = {}, 0
        for k in covered:
            cols = []
            for u, inv in dicts[d, k]:
                cols.append(remap[off:off + len(u)][inv].astype(np.int32))
                off += len(u)
            grid[k] = (np.stack(cols, axis=1) if cols
                       else np.zeros((len(covered[k][1]), 0), np.int32))
        M = max(int(n_valid.max()) if N else 0, 1)
        V = global_lengths.shape[0]
        global_codes = np.full((N, M), -1, np.int32)
        present = np.zeros(V, bool)
        rank = np.zeros(V, np.int32)
        locals_: List[Tuple[np.ndarray, np.ndarray]] = []
        for i, (src, _) in enumerate(sources):
            g = grid[id(src)][pos[i]].reshape(-1)
            global_codes[i, :n_valid[i]] = g
            present[g] = True
            lu = np.flatnonzero(present)
            present[lu] = False
            rank[lu] = np.arange(len(lu), dtype=np.int32)
            locals_.append((lu, rank[g]))
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
