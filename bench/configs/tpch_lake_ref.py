"""Plain reference for the TPC-H lake: G-PART (the paper's Algorithm 1,
pair by pair), partition tables, their serialized layout and sizes,
COMPREDICT's labels (ratios and decompression speeds measured with the
codecs themselves), its weighted-entropy features and its regression.
Straight Python and numpy; nothing of the program is imported. The cost
model and the bill are ``cost_ref``'s, over the whitelisted tiers.
"""

from __future__ import annotations

import heapq
import lzma
import time
import zlib
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np
import zstandard

Family = Tuple[FrozenSet[str], float]          # (files, reads)


# ------------------------------------------------------------------ G-PART
class Spans:
    """File-set span: file sizes summed in sorted file order."""

    def __init__(self, sizes: Dict[str, float]):
        self.sizes = sizes
        self.memo: Dict[FrozenSet[str], float] = {}

    def __call__(self, files: FrozenSet[str]) -> float:
        v = self.memo.get(files)
        if v is None:
            v = 0.0
            for f in sorted(files):
                v += self.sizes[f]
            self.memo[files] = v
        return v


def g_part(families: Sequence[Family], sizes: Dict[str, float],
           s_thresh_mult: float, rho_c: float, rho_c_abs: float,
           ) -> List[Family]:
    """Greedy merging of query families: repeatedly merge the pair of
    largest fractional overlap ``(|a| + |b| - |a u b|) / |a u b|`` among
    pairs whose reads are comparable (ratio <= rho_c or difference <=
    rho_c_abs), re-offering a merge product while its span is under
    ``s_thresh_mult`` times the median family span. Ties go to the lower
    ids (families first, then merge products in creation order)."""
    span = Spans(sizes)
    s_thresh = s_thresh_mult * float(np.median([span(f) for f, _ in
                                                families]))
    live: Dict[int, Family] = dict(enumerate(families))
    nxt = len(families)
    heap: List[Tuple[float, int, int]] = []

    def comparable(a: float, b: float) -> bool:
        hi, lo = max(a, b), max(min(a, b), 1e-12)
        return hi / lo <= rho_c or abs(a - b) <= rho_c_abs

    def weight(a: FrozenSet[str], b: FrozenSet[str]) -> float:
        if not (a & b):
            return 0.0
        u = span(a | b)
        return (span(a) + span(b) - u) / max(u, 1e-12)

    def offer(i: int, j: int) -> None:
        (fa, ra), (fb, rb) = live[i], live[j]
        if comparable(ra, rb):
            w = weight(fa, fb)
            if w > 0.0:
                heapq.heappush(heap, (-w, min(i, j), max(i, j)))

    ids = list(live)
    for x in range(len(ids)):
        for y in range(x + 1, len(ids)):
            offer(ids[x], ids[y])
    dead = set()
    while heap:
        _, i, j = heapq.heappop(heap)
        if i in dead or j in dead:
            continue
        (fa, ra), (fb, rb) = live[i], live[j]
        if not comparable(ra, rb):
            continue
        dead.update((i, j))
        del live[i], live[j]
        live[nxt] = (fa | fb, ra + rb)
        if span(fa | fb) < s_thresh:
            for k in list(live):
                if k != nxt:
                    offer(nxt, k)
        nxt += 1
    return list(live.values())


# ------------------------------------------------------- partition tables
def render(col: np.ndarray) -> np.ndarray:
    """A column's values as the strings the lake stores: floats with four
    decimals, integers in decimal, strings as they are."""
    if col.dtype.kind == "f":
        return np.char.mod("%.4f", col)
    if col.dtype.kind in "iu":
        return np.char.mod("%d", col)
    return col.astype(str)


def dtype_class(col: np.ndarray) -> str:
    return {"f": "float", "i": "int", "u": "int"}.get(col.dtype.kind, "str")


def partition_columns(files: FrozenSet[str], file_rows: dict,
                      ) -> Dict[str, np.ndarray]:
    """The partition's rows: its files' rows of the table it reads most,
    in row order, as ``{column: values}``."""
    by_table: Dict[str, list] = {}
    tables = {}
    for f in sorted(files):
        table, idx = file_rows[f]
        by_table.setdefault(table.name, []).append(idx)
        tables[table.name] = table
    name = max(by_table, key=lambda n: sum(len(i) for i in by_table[n]))
    rows = np.sort(np.concatenate(by_table[name]))
    return {k: v[rows] for k, v in tables[name].columns.items()}


def col_layout(columns: Dict[str, np.ndarray]) -> bytes:
    """The column-major layout: per column a ``#name`` line, then one line
    per value."""
    return b"".join(f"#{name}\n".encode() + ("\n".join(render(v).tolist())
                                             + "\n").encode()
                    for name, v in columns.items())


def col_layout_bytes(columns: Dict[str, np.ndarray]) -> int:
    """Size of the column-major layout: per column a ``#name`` line, then
    one line per value."""
    total = 0
    for name, v in columns.items():
        s = render(v)
        total += len(f"#{name}\n") + int(np.char.str_len(s).sum()) \
            + max(len(s), 1)
    return total


def describe(columns: Dict[str, np.ndarray], dtype=np.float64) -> tuple:
    """``(size, features, shape)`` of one partition, each column rendered
    once. ``size`` is ``col_layout_bytes``. The features are
    [log1p(size), log1p(rows), size/rows] then, per class int, float,
    str: weighted entropy -sum len(s) p(s) log p(s), entropy -sum p log p,
    distinct share, mean length sum len(s) p(s), columns; zeros for a
    class with no column. ``p`` is a value's share among all values of
    the class in the partition. ``shape`` is per class (values, distinct
    values)."""
    c = lambda x: np.asarray(x, np.float64).astype(dtype)
    rendered = {name: render(v) for name, v in columns.items()}
    size = sum(len(f"#{name}\n") + int(np.char.str_len(s).sum())
               + max(len(s), 1) for name, s in rendered.items())
    rows = max(len(next(iter(columns.values()))), 1)
    out = [np.log1p(c(size)), np.log1p(c(rows)), c(size) / c(rows)]
    shape = {}
    for d in ("int", "float", "str"):
        cols = [rendered[k] for k, v in columns.items()
                if dtype_class(v) == d]
        if not cols:
            out += [c(0.0)] * 5
            continue
        vals = np.concatenate(cols)
        uniq, counts = np.unique(vals, return_counts=True)
        shape[d] = (len(vals), len(uniq))
        p = c(counts) / c(len(vals))
        lens = c(np.char.str_len(uniq))
        logp = np.log(p).astype(dtype)
        out += [-(lens * p * logp).sum(dtype=dtype),
                -(p * logp).sum(dtype=dtype),
                c(len(uniq)) / c(len(vals)),
                (lens * p).sum(dtype=dtype), c(len(cols))]
    return size, np.array([float(x) for x in out]), shape


# -------------------------------------------------------------- COMPREDICT
def _zstd(level: int):
    return (zstandard.ZstdCompressor(level=level).compress,
            zstandard.ZstdDecompressor().decompress)


CODECS = {
    "zlib-1": (lambda b: zlib.compress(b, 1), zlib.decompress),
    "zstd-3": _zstd(3),
    "zstd-19": _zstd(19),
    "lzma-1": (lambda b: lzma.compress(b, preset=1), lzma.decompress),
}


def labels(samples: Sequence[Dict[str, np.ndarray]], schemes: Sequence[str],
           repeats: int = 3) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Per scheme, each sample's compression ratio (raw over compressed
    bytes of its column-major layout) and decompression seconds per GB
    (the best of ``repeats`` timed decompressions)."""
    out = {}
    raws = [col_layout(c) for c in samples]
    for s in schemes:
        compress, decompress = CODECS[s]
        ratio, speed = [], []
        for raw in raws:
            comp = compress(raw)
            best = np.inf
            for _ in range(repeats):
                t0 = time.perf_counter()
                decompress(comp)
                best = min(best, time.perf_counter() - t0)
            ratio.append(len(raw) / max(len(comp), 1))
            speed.append(best / (max(len(raw), 1) / 1e9))
        out[s] = (np.array(ratio), np.array(speed))
    return out


class Ridge:
    """The configuration's model, per scheme and target: RBF kernel ridge
    regression on the features standardized by the samples' mean and
    standard deviation (plus 1e-8), kernel ``exp(-|a - b|^2 / F)`` for F
    features, coefficients from ``(K + alpha I) c = y``. Predicted ratios
    are at least 1 and times at least 0; scheme ``none`` is (1, 0)."""

    def __init__(self, X: np.ndarray, labels: dict, alpha: float):
        X = np.asarray(X, np.float64)
        self.mu, self.sd = X.mean(0), X.std(0) + 1e-8
        self.Z = (X - self.mu) / self.sd
        self.g = 1.0 / X.shape[1]
        A = self._kernel(self.Z) + alpha * np.eye(len(X))
        self.coef = {s: tuple(np.linalg.solve(A, np.asarray(y, np.float64))
                              for y in ys) for s, ys in labels.items()}

    def _kernel(self, Z: np.ndarray) -> np.ndarray:
        d2 = ((Z[:, None, :] - self.Z[None, :, :]) ** 2).sum(-1)
        return np.exp(-self.g * d2)

    def predict(self, x, schemes: Sequence[str]) -> Tuple[np.ndarray,
                                                          np.ndarray]:
        """(ratios, decompression s/GB) of one partition, per scheme."""
        z = (np.asarray(x, np.float64) - self.mu) / self.sd
        k = self._kernel(z[None, :])[0]
        R, D = np.ones(len(schemes)), np.zeros(len(schemes))
        for i, s in enumerate(schemes):
            if s != "none":
                R[i] = max(float(k @ self.coef[s][0]), 1.0)
                D[i] = max(float(k @ self.coef[s][1]), 0.0)
        return R, D
