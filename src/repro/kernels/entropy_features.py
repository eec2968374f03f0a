"""COMPREDICT entropy feature kernels.

The paper's feature pass is a full scan of each partition (its stated
one-time compute cost, §V). Two device-resident primitives live here:

* :func:`byte_entropy` — byte histogram + Shannon entropy of one payload.
  The payload is laid out as (rows, 128) lanes; each row is compared
  against a (256, 128) symbol iota and the matches accumulate
  lane-parallel in VMEM scratch across the sequential grid axis.
* :func:`weighted_entropy_features` — the batched COMPREDICT pipeline:
  per-dtype-class weighted entropy H(P,d), plain entropy, distinct
  fraction, and mean value length for N partitions at once, plus the
  bucketed successive-20%-of-rows entropy variant, with ragged-length and
  pad masking. One linear scatter-add builds every partition's
  (n_buckets, vocab) histogram (an M x V one-hot would cost ~1e14
  compares at TPC-H SF 1); the grid kernel (partitions × vocabulary
  tiles) then sums the separable entropy terms tile by tile and reduces
  them on each partition's final tile.
  :func:`weighted_entropy_features_ref` is the ``jax.vmap``-based
  pure-jnp oracle with identical semantics.

Inputs for the batched form come from
:func:`repro.data.tables.encode_dtype_classes` (shared-vocabulary int32
codes, row-major within a partition); the consumer-facing seam is
``repro.core.compredict.extract_features_batch`` (see ``docs/engine.md``,
"Feature backends"). Weighted entropy uses the natural log to match
``repro.core.compredict.weighted_entropy``; :func:`byte_entropy` reports
bits/byte (log2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _byte_kernel(d_ref, hist_ref, ent_ref, acc_scr, *, rows: int, n: int):
    """Grid (row block,), sequential. Each (1, 128) row of bytes is compared
    against a (256, 128) symbol iota, so counts accumulate lane-parallel in
    ``acc_scr`` and are lane-reduced once, on the final block."""
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    sym = jax.lax.broadcasted_iota(jnp.int32, (256, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def body(r, carry):
        x = d_ref[pl.ds(r, 1), :]                              # (1, 128)
        pos = (bi * rows + r) * _LANES + lane
        x = jnp.where(pos < n, x, -1)                    # pads match no symbol
        acc_scr[...] += (sym == x).astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, rows, body, 0)

    @pl.when(bi == pl.num_programs(0) - 1)
    def _finalize():
        h = acc_scr[...].sum(axis=1, keepdims=True)            # (256, 1)
        hist_ref[...] = h.astype(jnp.int32)
        p = h / jnp.float32(max(n, 1))
        plogp = jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0)
        ent = -plogp.sum(axis=0, keepdims=True)                # (1, 1)
        ent_ref[...] = jnp.broadcast_to(ent, ent_ref.shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def byte_entropy(data, *, block: int = 8192, interpret: bool = False):
    """data: (n,) uint8 -> (hist (256,) int32, entropy bits/byte scalar).

    The payload is laid out as (rows, 128) int32 and swept in blocks of
    ``block`` bytes (rounded to whole (8, 128) tiles)."""
    n = data.shape[0]
    rows = _round_up(max(min(block, max(n, 1)), 1), 8 * _LANES) // _LANES
    n_pad = _round_up(max(n, 1), rows * _LANES)
    d = jnp.pad(data.astype(jnp.int32), (0, n_pad - n)).reshape(-1, _LANES)
    kernel = functools.partial(_byte_kernel, rows=rows, n=n)
    hist, ent = pl.pallas_call(
        kernel,
        grid=(d.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, _LANES), lambda bi: (bi, 0))],
        out_specs=[pl.BlockSpec((256, 1), lambda bi: (0, 0)),
                   pl.BlockSpec((1, _LANES), lambda bi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((256, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((256, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(d)
    return hist[:, 0], ent[0, 0]


# ---------------------------------------------- batched weighted entropy
_SUMMARY_ROW, _BUCKET_ROW = 0, 8        # output rows: 4 summary, nb bucket


def _wef_kernel(cnt_ref, len_ref, tot_ref, totb_ref, out_ref, acc_scr,
                accb_scr):
    """Grid (partition, vocabulary tile); the tile axis is sequential.

    Every entropy term is separable per vocabulary entry, so each tile adds
    its lane-wise partial sums into the scratch; the totals that normalize
    p come in precomputed (``tot_ref`` / ``totb_ref``), never from the
    histogram. Features are lane-reduced once, on the final tile."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accb_scr[...] = jnp.zeros_like(accb_scr)

    cnt = cnt_ref[...].astype(jnp.float32)            # (nb, bv) per bucket
    lens = len_ref[...]                               # (1, bv)
    hist = cnt.sum(axis=0, keepdims=True)             # (1, bv)
    p = hist / tot_ref[...]
    plogp = jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)), 0.0)
    acc_scr[0:1, :] += -lens * plogp                  # H(P,d)
    acc_scr[1:2, :] += -plogp                         # plain H
    acc_scr[2:3, :] += (hist > 0).astype(jnp.float32)  # distinct count
    acc_scr[3:4, :] += lens * p                       # mean length
    pb = cnt / totb_ref[...]
    accb_scr[...] += -lens * jnp.where(
        pb > 0, pb * jnp.log(jnp.maximum(pb, 1e-30)), 0.0)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _finalize():
        s = acc_scr[...].sum(axis=1, keepdims=True)   # (4, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(row == 2, s / tot_ref[...], s)  # distinct fraction
        out_ref[...] = jnp.zeros_like(out_ref)
        out_ref[_SUMMARY_ROW:_SUMMARY_ROW + 4, :] = jnp.broadcast_to(
            s, (4, _LANES))
        nb = accb_scr.shape[0]
        out_ref[_BUCKET_ROW:_BUCKET_ROW + nb, :] = jnp.broadcast_to(
            accb_scr[...].sum(axis=1, keepdims=True), (nb, _LANES))


def _as_batched_lengths(lengths, N: int) -> jnp.ndarray:
    """(V,) shared vocab -> (N, V); (N, Vmax) per-partition passes through."""
    lengths = jnp.asarray(lengths, jnp.float32)
    if lengths.ndim == 1:
        lengths = jnp.broadcast_to(lengths[None, :], (N, lengths.shape[0]))
    return lengths


def _bucket_starts(n_rows, n_cols, n_buckets: int):
    """First row-major value position of each bucket b >= 1: bucket b
    spans rows [floor(b*nr/nb), floor((b+1)*nr/nb)), the last bucket
    everything after its first row."""
    nc = jnp.maximum(n_cols, 1)
    return [((b * n_rows) // n_buckets) * nc for b in range(1, n_buckets)]


def _bucket_totals(n_valid, n_rows, n_cols, n_buckets: int):
    """(N, n_buckets) values per bucket, from the bucket edges alone (the
    positions below ``n_valid`` that fall in each bucket)."""
    edges = ([jnp.zeros_like(n_valid)]
             + [jnp.minimum(s, n_valid)
                for s in _bucket_starts(n_rows, n_cols, n_buckets)]
             + [n_valid])
    return jnp.stack([edges[b + 1] - edges[b] for b in range(n_buckets)],
                     axis=1)


def _histogram_index(codes, n_valid, n_rows, n_cols, n_buckets: int):
    """(row, col) scatter indices of every code into the (N * n_buckets,
    vocab) histogram: row ``partition * n_buckets + bucket``, col the code.
    The two axes stay separate (a flattened int32 key would wrap once
    N * n_buckets * vocab reaches 2**31); pads and positions past
    ``n_valid`` get row ``N * n_buckets``, which the scatter drops."""
    N, M = codes.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (N, M), 1)
    part = jax.lax.broadcasted_iota(jnp.int32, (N, M), 0)
    valid = (pos < n_valid[:, None]) & (codes >= 0)
    bucket = jnp.zeros_like(pos)
    for start in _bucket_starts(n_rows, n_cols, n_buckets):
        bucket += (pos >= start[:, None]).astype(jnp.int32)
    row = jnp.where(valid, part * n_buckets + bucket, N * n_buckets)
    return row, jnp.where(valid, codes, 0)


@functools.partial(jax.jit, static_argnames=("n_buckets", "block",
                                             "interpret"))
def weighted_entropy_features(codes, n_valid, n_rows, n_cols, lengths, *,
                              n_buckets: int = 1, block: int = 16384,
                              interpret: bool = False):
    """Batched per-partition weighted-entropy features, one device dispatch.

    codes: (N, M) int32 value codes, -1 padded; n_valid / n_rows / n_cols:
    (N,) int32 ragged-shape metadata; lengths: per-slot string lengths,
    either (N, Vmax) local vocabularies (what
    :func:`repro.data.tables.encode_dtype_classes` produces — histogram
    width stays at the per-partition cardinality) or a (V,) vocabulary
    shared by every partition.

    The per-bucket histograms are one linear scatter-add over all N*M
    codes (no M x V one-hot); the Pallas kernel then reduces them in
    ``block``-wide vocabulary tiles (rounded to 128 lanes), so VMEM holds a
    few tiles whatever the vocabulary width.

    Returns ``(summary (N, 4) f32, bucket_H (N, n_buckets) f32)`` where the
    summary columns are [weighted entropy H(P,d), plain entropy, distinct
    fraction, mean value length] — natural-log, matching
    ``repro.core.compredict.weighted_entropy`` / ``_entropy_block`` — and
    ``bucket_H[:, b]`` is the weighted entropy of the b-th 1/n_buckets of
    rows (``repro.core.compredict.bucketed_weighted_entropy``).
    """
    codes = jnp.asarray(codes, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    n_rows = jnp.asarray(n_rows, jnp.int32)
    n_cols = jnp.asarray(n_cols, jnp.int32)
    N, M = codes.shape
    lengths = _as_batched_lengths(lengths, N)
    V = lengths.shape[1]
    bv = min(_round_up(block, _LANES), _round_up(V, _LANES))
    vpad = _round_up(V, bv)
    nb = n_buckets

    row, col = _histogram_index(codes, n_valid, n_rows, n_cols, nb)
    counts = jnp.zeros((N * nb, vpad), jnp.int32).at[row, col].add(
        1, mode="drop").reshape(N, nb, vpad)
    lens = jnp.pad(lengths, ((0, 0), (0, vpad - V))).reshape(N, 1, vpad)
    tot = jnp.maximum(n_valid, 1).astype(jnp.float32).reshape(N, 1, 1)
    tot_b = jnp.maximum(_bucket_totals(n_valid, n_rows, n_cols, nb), 1
                        ).astype(jnp.float32).reshape(N, nb, 1)
    out_rows = _BUCKET_ROW + _round_up(nb, 8)
    out = pl.pallas_call(
        _wef_kernel,
        grid=(N, vpad // bv),
        in_specs=[pl.BlockSpec((None, nb, bv), lambda i, v: (i, 0, v)),
                  pl.BlockSpec((None, 1, bv), lambda i, v: (i, 0, v)),
                  pl.BlockSpec((None, 1, 1), lambda i, v: (i, 0, 0)),
                  pl.BlockSpec((None, nb, 1), lambda i, v: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, out_rows, _LANES),
                               lambda i, v: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, out_rows, _LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4, bv), jnp.float32),
                        pltpu.VMEM((nb, bv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(counts, lens, tot, tot_b)
    return (out[:, _SUMMARY_ROW:_SUMMARY_ROW + 4, 0],
            out[:, _BUCKET_ROW:_BUCKET_ROW + nb, 0])


def weighted_entropy_features_ref(codes, n_valid, n_rows, n_cols, lengths, *,
                                  n_buckets: int = 1):
    """Pure-jnp oracle for :func:`weighted_entropy_features`: one partition
    is a (n_buckets, V) scatter-add histogram + entropy reduction, vmapped
    over the batch. Jit-able with ``n_buckets`` static."""
    codes = jnp.asarray(codes, jnp.int32)
    N, M = codes.shape
    lengths = _as_batched_lengths(lengths, N)
    V = lengths.shape[1]
    nb = n_buckets

    def one(code_row, nv, nr, nc, lens):
        pos = jnp.arange(M, dtype=jnp.int32)
        valid = pos < nv
        safe = jnp.where(valid, code_row, 0)
        if nb == 1:
            bucket = jnp.zeros(M, jnp.int32)
        else:
            row = pos // jnp.maximum(nc, 1)
            edges = (jnp.arange(1, nb, dtype=jnp.int32) * nr) // nb
            bucket = (row[:, None] >= edges[None, :]).sum(axis=1)
        hist_b = jnp.zeros((nb, V), jnp.float32).at[bucket, safe].add(
            valid.astype(jnp.float32))
        hist = hist_b.sum(axis=0)
        total = jnp.maximum(nv.astype(jnp.float32), 1.0)
        p = hist / total
        plogp = jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)), 0.0)
        summary = jnp.stack([
            -jnp.sum(lens * plogp),
            -jnp.sum(plogp),
            jnp.sum((hist > 0).astype(jnp.float32)) / total,
            jnp.sum(lens * p)])
        tot_b = jnp.maximum(hist_b.sum(axis=1, keepdims=True), 1.0)
        pb = hist_b / tot_b
        plogpb = jnp.where(pb > 0, pb * jnp.log(jnp.maximum(pb, 1e-30)), 0.0)
        return summary, -(lens[None, :] * plogpb).sum(axis=1)

    return jax.vmap(one)(codes, jnp.asarray(n_valid, jnp.int32),
                         jnp.asarray(n_rows, jnp.int32),
                         jnp.asarray(n_cols, jnp.int32), lengths)
