"""Paper Tables V-VIII + Fig 4 — COMPREDICT prediction quality, plus the
feature-backend sweep (:func:`run_features`, registered as ``features`` in
``benchmarks/run.py``).

V    : training-data (random vs queries) x features (size vs weighted
       entropy) ablation, gzip-class codec;
VI   : compression-ratio prediction, models x schemes x layouts (TPC-H 1GB);
VII  : ratio prediction on larger/skewed TPC-H;
VIII : decompression-speed prediction.
"""

import time

import numpy as np

from benchmarks.common import emit, row, timed
from repro.core.compredict import (build_dataset, extract_features_batch,
                                   query_samples, random_samples, train_eval)
from repro.data import tpch
from repro.data.tables import Table, encode_dtype_classes
from repro.storage.codecs import codec_by_name

SCHEMES_V1 = [("zlib-6", "row"), ("zstd-3", "row"), ("zlib-6", "col"),
              ("zstd-3", "col"), ("lzma-1", "col")]
MODELS = ["Averaging", "XGBoostless", "NeuralNetwork", "SVR", "RandomForest"]


def _mk_samples(scale_rows, skew, seed, n_per_template=8):
    db = tpch.generate(scale_rows=scale_rows, skew=skew, seed=seed)
    qs = tpch.generate_queries(db, n_per_template=n_per_template,
                               seed=seed + 1)
    return db, qs, query_samples(qs, db.tables, max_rows=1500)


def run():
    rows = []
    db, qs, samples = _mk_samples(5000, 0.0, 0)

    # ---- Table V: sampling x features (gzip ~ zlib-6, row layout)
    codec = codec_by_name("zlib-6")
    rand = random_samples(db.tables["lineitem"], 60, 900, seed=3)
    for train_data, samp in (("random", rand), ("queries", samples)):
        for feats in ("size", "weighted_entropy"):
            if train_data == "random" and feats == "size":
                continue
            for target in ("ratio", "dspeed"):
                ds = build_dataset(samp, codec, "row", feats)
                (_, res), us = timed(
                    lambda d=ds, t=target: train_eval(d, "RandomForest", t),
                    repeats=1)
                rows.append(row(
                    f"tableV/{train_data}/{feats}/{target}", us,
                    mae=round(res.mae, 4), mape=round(res.mape, 3),
                    r2=round(res.r2, 4)))

    # ---- Fig 4: query samples compress better than random rows
    ds_q = build_dataset(query_samples(
        [q for q in qs if q.table == "lineitem"], db.tables, 900),
        codec, "row")
    ds_r = build_dataset(rand, codec, "row")
    rows.append(row("fig4/ratio_mean", 0,
                    queries=round(float(ds_q.ratio.mean()), 3),
                    random=round(float(ds_r.ratio.mean()), 3)))

    # ---- Table VI: models x schemes x layouts, ratio (TPC-H '1GB')
    for scheme, layout in SCHEMES_V1:
        ds = build_dataset(samples, codec_by_name(scheme), layout)
        for model in ("Averaging", "NeuralNetwork", "SVR", "RandomForest"):
            (_, res), us = timed(
                lambda d=ds, m=model: train_eval(d, m, "ratio"), repeats=1)
            rows.append(row(f"tableVI/{scheme}+{layout}/{model}", us,
                            mae=round(res.mae, 4), mape=round(res.mape, 3),
                            r2=round(res.r2, 4)))

    # ---- Table VII: '100GB' (larger scale) + Zipf-skew variants
    for tag, (scale, skew) in (("100GB", (20000, 0.0)),
                               ("Skew", (5000, 1.2))):
        _, _, samp = _mk_samples(scale, skew, seed=11, n_per_template=6)
        for scheme, layout in (("zlib-6", "row"), ("zlib-6", "col")):
            ds = build_dataset(samp, codec_by_name(scheme), layout)
            for model in ("Averaging", "SVR", "RandomForest"):
                (_, res), us = timed(
                    lambda d=ds, m=model: train_eval(d, m, "ratio"),
                    repeats=1)
                rows.append(row(
                    f"tableVII/{tag}/{scheme}+{layout}/{model}", us,
                    mae=round(res.mae, 4), mape=round(res.mape, 3),
                    r2=round(res.r2, 4)))

    # ---- Table VIII: decompression sec/GB prediction
    for scheme, layout in (("zlib-6", "row"), ("zlib-6", "col"),
                           ("lzma-1", "col")):
        ds = build_dataset(samples, codec_by_name(scheme), layout)
        for model in ("Averaging", "SVR", "RandomForest"):
            (_, res), us = timed(
                lambda d=ds, m=model: train_eval(d, m, "dspeed"), repeats=1)
            rows.append(row(f"tableVIII/{scheme}+{layout}/{model}", us,
                            mae=round(res.mae, 4), mape=round(res.mape, 3),
                            r2=round(res.r2, 4)))
    return emit(rows, "tablesV-VIII_compredict")


# ------------------------------------------------- feature-backend sweep
def _synthetic_partitions(n_parts: int, n_rows: int, seed: int = 0):
    """Mixed-dtype partitions sized like query-result samples."""
    rng = np.random.default_rng(seed)
    strs = np.array([f"v{i}" for i in range(40)])
    out = []
    for i in range(n_parts):
        n = n_rows + int(rng.integers(0, n_rows // 2 + 1))
        out.append(Table(f"p{i}", {
            "a": rng.integers(0, 50, n),
            "b": rng.integers(0, 1000, n),
            "x": rng.normal(size=n).round(2),
            "y": rng.normal(size=n),
            "s": rng.choice(strs[:5], n),
            "t": rng.choice(strs, n),
        }))
    return out


def run_features():
    """NumPy loop vs batched device extraction (kind='bucketed', the full
    COMPREDICT feature set). 'jnp_extract' is the per-batch hot-path cost
    once partitions are dictionary-encoded (the paper's one-time pass,
    reported separately as 'encode'); acceptance bar: >= 10x over the NumPy
    loop at N >= 500 on CPU jit alone."""
    rows = []
    for N, n_rows in ((64, 150), (200, 150), (500, 150), (1000, 150)):
        tabs = _synthetic_partitions(N, n_rows, seed=N)
        sizes = [t.nbytes("col") for t in tabs]
        _, us_np = timed(lambda: extract_features_batch(
            tabs, "col", "bucketed", "numpy", sizes=sizes), repeats=1)
        enc, us_enc = timed(lambda: encode_dtype_classes(tabs), repeats=1)
        fn = lambda: extract_features_batch(          # noqa: E731
            tabs, "col", "bucketed", "jnp", sizes=sizes, encoded=enc)
        fn()                                          # warm the jit cache
        _, us_jnp = timed(fn, repeats=3)
        _, us_tot = timed(lambda: extract_features_batch(
            tabs, "col", "bucketed", "jnp", sizes=sizes), repeats=1)
        rows.append(row(f"features/N{N}/numpy_loop", us_np))
        rows.append(row(f"features/N{N}/encode_once", us_enc))
        rows.append(row(f"features/N{N}/jnp_extract", us_jnp,
                        speedup_vs_numpy=round(us_np / us_jnp, 1)))
        rows.append(row(f"features/N{N}/jnp_encode_plus_extract", us_tot,
                        speedup_vs_numpy=round(us_np / us_tot, 1)))
    # Pallas interpret mode is a correctness vehicle, not a CPU fast path:
    # record its overhead at small N so regressions are visible.
    tabs = _synthetic_partitions(32, 100, seed=1)
    enc = encode_dtype_classes(tabs)
    t0 = time.perf_counter()
    extract_features_batch(tabs, "col", "bucketed", "interpret", encoded=enc)
    rows.append(row("features/N32/pallas_interpret",
                    (time.perf_counter() - t0) * 1e6))
    return emit(rows, "feature_backends")


if __name__ == "__main__":
    run()
    run_features()
