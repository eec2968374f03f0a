"""DATAPART fractional-overlap matrix kernels (paper §VI, G-PART edges).

G-PART's candidate graph needs, for every partition pair (i, j), the span
of their file intersection. On device this is a blocked one-hot matmul:
the code rows become dense bf16 file-indicator matrices, and a
(block_i, block_f) slab scaled by *file sizes* contracts against a
(block_j, block_f) indicator slab for partition j — their product is
exactly ``sum(sizes[c] for c in codes_i & codes_j)`` and rides the MXU.
The file axis is the innermost sequential grid dimension, accumulating
into a (block_i, block_j) VMEM scratch; ``-1`` pad codes match no file
column, which is the whole ragged-masking story. The f32 contraction runs
at ``Precision.HIGHEST`` (a single bf16 pass would round the sizes);
G-PART only reads which weights are positive and recomputes the heap
weights in f64, so its partitions do not depend on the kernel's rounding.

Three implementations, dispatched through
:func:`repro.kernels.ops.fractional_overlap_matrix`:

* :func:`fractional_overlap_matrix` — the Pallas TPU kernel (``interpret``
  runs the same program on the CPU);
* :func:`fractional_overlap_matrix_ref` — vmapped-jnp oracle (scatter-add
  one-hot rows, one einsum);
* :func:`fractional_overlap_matrix_np` — numpy fallback, also the shape
  oracle for the host-side blocked sweep in
  ``repro.core.datapart.PartitionIndex.overlap_matrix``.

All three accept an optional second operand (``codes_b``/``spans_b``) so a
row block can sweep against the full set — the rectangular form the
sharded path (``repro.core.datapart._overlap_matrix_sharded``) shards over
devices. Weights are finalized outside the kernel:
``w = inter / (span_a + span_b - inter)`` with an exact 0 wherever the
intersection is empty (``inter == 0`` propagates — no fp residue can link
disjoint partitions, the PYTHONHASHSEED bug class from PR 2).

Scale note: the dense (N, N) sweep is for moderate N (device dispatch
instead of N^2 Python). For N >= 1e6 files use
``PartitionIndex.candidate_pairs`` (inverted-index join / MinHash-style
row sampling) — that path never materializes a matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _finalize_weights(inter, spans_a, spans_b):
    """inter -> fractional overlap; exact 0 for empty intersections."""
    den = spans_a[:, None] + spans_b[None, :] - inter
    return jnp.where(inter > 0.0, inter / jnp.maximum(den, 1e-12), 0.0)


# ------------------------------------------------------------ pallas kernel
def _overlap_kernel(a_ref, sz_ref, b_ref, out_ref, acc_scr):
    """Grid (i block, j block, file block); file axis sequential."""
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a = a_ref[...].astype(jnp.float32) * sz_ref[...]   # sizes at i's files
    b = b_ref[...].astype(jnp.float32)                  # indicator for j
    # f32 at HIGHEST: file sizes would lose bits in a single bf16 pass
    acc_scr[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(fi == pl.num_programs(2) - 1)
    def _finalize():
        out_ref[...] = acc_scr[...]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _indicator(codes, n_rows: int, n_files: int):
    """(n_rows, n_files) bf16 0/1 membership of ascending ``-1``-padded
    code rows. A binary search per (row, file) instead of a scatter: on
    the TPU a scatter lowers to a sort, which takes ~25 s to compile."""
    codes = jnp.pad(codes, ((0, n_rows - codes.shape[0]), (0, 0)),
                    constant_values=-1)
    keys = jnp.where(codes >= 0, codes, jnp.iinfo(jnp.int32).max)
    files = jnp.arange(n_files, dtype=jnp.int32)

    def row(r):
        pos = jnp.minimum(jnp.searchsorted(r, files), r.shape[0] - 1)
        return (r[pos] == files).astype(jnp.bfloat16)
    return jax.vmap(row)(keys)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j",
                                             "block_f", "interpret"))
def fractional_overlap_matrix(codes, sizes, spans, *, codes_b=None,
                              spans_b=None, block_i: int = 128,
                              block_j: int = 128, block_f: int = 2048,
                              interpret: bool = False):
    """(NA, NB) f32 fractional-overlap matrix from ``-1``-padded code rows.

    codes: (NA, M) int32 ascending file codes per partition, -1 padded
    (``PartitionIndex.padded_codes`` layout); sizes: (F,) f32 per-code file
    sizes; spans: (NA,) f32 partition spans. ``codes_b``/``spans_b``
    default to the first operand (square, symmetric sweep).

    The code rows become dense bf16 file-indicator matrices (a binary
    search per row: rows must be ascending, as ``padded_codes`` emits
    them, or membership comes out wrong without an error); the kernel
    then contracts (block_i, block_f) x (block_j, block_f) tiles, so VMEM
    holds three tiles whatever the widest family's M. Rows pad to
    ``block_i`` / ``block_j`` (multiples of 128) and files to ``block_f``.

    Memory: the indicators take 2 bytes per (row, file) of HBM (2 * NA * F
    for the square sweep), growing with the lake's total files F rather
    than with M — 9 MB at TPC-H SF 1
    (256 x 17.4k), 20 GB for 1e4 families over 1e6 files. Past one
    chip's HBM use the inverted-index or sampled candidate paths
    (``PartitionIndex.candidate_pairs``).
    """
    codes = jnp.asarray(codes, jnp.int32)
    spans = jnp.asarray(spans, jnp.float32)
    sizes = jnp.asarray(sizes, jnp.float32)
    na = codes.shape[0]
    n_files = int(sizes.shape[0])
    block_f = min(block_f, _round_up(max(n_files, 1), 128))
    f_pad = _round_up(max(n_files, 1), block_f)
    ra = _round_up(max(na, 1), block_i)
    ind_a = _indicator(codes, ra, f_pad)
    if codes_b is None:
        nb, spans_b, ind_b, rb, block_j = na, spans, ind_a, ra, block_i
    else:
        nb = codes_b.shape[0]
        spans_b = jnp.asarray(spans_b, jnp.float32)
        rb = _round_up(max(nb, 1), block_j)
        ind_b = _indicator(jnp.asarray(codes_b, jnp.int32), rb, f_pad)
    sz = jnp.pad(sizes, (0, f_pad - n_files)).reshape(1, f_pad)
    inter = pl.pallas_call(
        _overlap_kernel,
        grid=(ra // block_i, rb // block_j, f_pad // block_f),
        in_specs=[pl.BlockSpec((block_i, block_f), lambda i, j, f: (i, f)),
                  pl.BlockSpec((1, block_f), lambda i, j, f: (0, f)),
                  pl.BlockSpec((block_j, block_f), lambda i, j, f: (j, f))],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j, f: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ra, rb), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, block_j), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ind_a, sz, ind_b)[:na, :nb]
    return _finalize_weights(inter, spans, spans_b)


# ------------------------------------------------------------- jnp oracle
def fractional_overlap_matrix_ref(codes, sizes, spans, *, codes_b=None,
                                  spans_b=None):
    """Vmapped-jnp oracle: scatter-add each code row into a dense (F,)
    one-hot (sizes on the A side, indicator on the B side), one matmul."""
    codes = jnp.asarray(codes, jnp.int32)
    spans = jnp.asarray(spans, jnp.float32)
    sizes = jnp.asarray(sizes, jnp.float32)
    if codes_b is None:
        codes_b, spans_b = codes, spans
    else:
        codes_b = jnp.asarray(codes_b, jnp.int32)
        spans_b = jnp.asarray(spans_b, jnp.float32)
    F = sizes.shape[0]

    def one_hot_row(row, weights):
        valid = row >= 0
        safe = jnp.where(valid, row, 0)
        w = jnp.where(valid, weights[safe], 0.0)
        return jnp.zeros(F, jnp.float32).at[safe].add(w)

    oh_a = jax.vmap(lambda r: one_hot_row(r, sizes))(codes)
    oh_b = jax.vmap(lambda r: one_hot_row(r, jnp.ones_like(sizes)))(codes_b)
    inter = oh_a @ oh_b.T
    return _finalize_weights(inter, spans, spans_b)


# ------------------------------------------------------------ numpy fallback
def fractional_overlap_matrix_np(codes, sizes, spans, *, codes_b=None,
                                 spans_b=None):
    """Numpy fallback with identical semantics (f64 accumulate, f32 out)."""
    codes = np.asarray(codes, np.int64)
    spans = np.asarray(spans, np.float64)
    sizes = np.asarray(sizes, np.float64)
    if codes_b is None:
        codes_b, spans_b = codes, spans
    else:
        codes_b = np.asarray(codes_b, np.int64)
        spans_b = np.asarray(spans_b, np.float64)
    F = sizes.shape[0]

    def one_hot(cs, weights):
        oh = np.zeros((cs.shape[0], F))
        r, c = np.nonzero(cs >= 0)
        oh[r, cs[r, c]] = weights[cs[r, c]]
        return oh

    inter = one_hot(codes, sizes) @ one_hot(codes_b, np.ones(F)).T
    den = spans[:, None] + spans_b[None, :] - inter
    out = np.where(inter > 0.0, inter / np.maximum(den, 1e-12), 0.0)
    return out.astype(np.float32)
