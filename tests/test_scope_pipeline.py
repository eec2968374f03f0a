"""End-to-end SCOPe pipeline + access prediction (paper §IV-C, §VII)."""

import dataclasses

import numpy as np
import pytest

from repro.core.access_predict import (optimal_tiers, predicted_tiers,
                                       train_tier_predictor)
from repro.core.compredict import CompressionPredictor, query_samples
from repro.core.costs import azure_table
from repro.core.engine import PlacementEngine
from repro.core.scope import ScopeConfig, paper_variants, run_pipeline
from repro.data import tpch
from repro.data.workloads import generate_workload
from repro.storage.codecs import available_schemes, codec_by_name


@pytest.fixture(scope="module")
def pipeline_inputs():
    db = tpch.generate(scale_rows=3000, seed=0)
    queries = tpch.generate_queries(db, n_per_template=3, seed=1)
    parts, file_rows = tpch.partitions_from_queries(db, queries)
    return parts, file_rows


def test_scope_beats_default(pipeline_inputs):
    parts, file_rows = pipeline_inputs
    table = azure_table()
    default = run_pipeline(parts, file_rows, table, ScopeConfig(
        use_partitioning=False, use_tiering=False, use_compression=False,
        fixed_tier=0, tier_whitelist=(0, 1, 2)))
    scope = run_pipeline(parts, file_rows, table, ScopeConfig(
        tier_whitelist=(0, 1, 2)))
    assert scope.total_cents < default.total_cents
    assert scope.n_partitions >= default.n_partitions  # G-PART splits datasets


def test_partitioning_reduces_read_cost(pipeline_inputs):
    parts, file_rows = pipeline_inputs
    table = azure_table()
    whole = run_pipeline(parts, file_rows, table, ScopeConfig(
        use_partitioning=False, use_tiering=False, use_compression=False,
        fixed_tier=0))
    parted = run_pipeline(parts, file_rows, table, ScopeConfig(
        use_partitioning=True, use_tiering=False, use_compression=False,
        fixed_tier=0))
    # paper Tables IX-XI rows 1 vs 5: partitioning slashes read cost
    assert parted.read_cents < whole.read_cents


def test_latency_sla_respected(pipeline_inputs):
    parts, file_rows = pipeline_inputs
    table = azure_table()
    rep = run_pipeline(parts, file_rows, table, ScopeConfig(
        latency_sla_sec=0.03, tier_whitelist=(0, 1, 2, 3)))
    # premium TTFB=0.0053 is the only tier under a 30ms SLA with decomp time
    assert rep.assignment.feasible
    assert rep.read_latency_ttfb <= 0.03


def test_paper_variant_grid(pipeline_inputs):
    parts, file_rows = pipeline_inputs
    table = azure_table()
    # small synthetic capacity: forces tiering decisions like Table XII
    total = sum(p.span for p in parts) / 1e9
    cap = np.array([total * 0.2, total * 0.4, total * 0.6, np.inf])
    variants = paper_variants(cap)
    results = {}
    for name in ["Default (store on premium)",
                 "Multi-Tiering [Hermes]",
                 "SCOPe (Total cost focused)"]:
        results[name] = run_pipeline(parts, file_rows, table, variants[name])
    assert results["SCOPe (Total cost focused)"].total_cents <= \
        results["Default (store on premium)"].total_cents
    # default premium latency is the floor
    assert results["Default (store on premium)"].read_latency_ttfb == \
        pytest.approx(0.0053)


def test_feature_backend_parity_end_to_end():
    """CompressStage with feature_backend='interpret' (the Pallas program
    in the interpreter) and 'jnp' must produce the *identical* PlacementPlan — same tiers, same
    schemes — as the NumPy feature loop on a seeded TPC-H-style workload."""
    db = tpch.generate(scale_rows=900, seed=2)
    queries = tpch.generate_queries(db, n_per_template=2, seed=3)
    parts, file_rows = tpch.partitions_from_queries(db, queries)
    schemes = available_schemes(("none", "zstd-3", "zlib-6", "zlib-1"))
    pred = CompressionPredictor(model_name="SVR").fit(
        query_samples(queries, db.tables, max_rows=300)[:40],
        layouts=("col",),
        codecs=[codec_by_name(s) for s in schemes if s != "none"])
    table = azure_table()
    base_cfg = ScopeConfig(schemes=schemes, predictor=pred,
                           tier_whitelist=(0, 1, 2))
    plans = {}
    for backend in ("numpy", "jnp", "interpret"):
        cfg = dataclasses.replace(base_cfg, feature_backend=backend)
        plans[backend] = PlacementEngine(table, cfg).run(parts, file_rows)
    for backend in ("jnp", "interpret"):
        np.testing.assert_array_equal(plans[backend].assignment.tier,
                                      plans["numpy"].assignment.tier)
        np.testing.assert_array_equal(plans[backend].assignment.scheme,
                                      plans["numpy"].assignment.scheme)
        assert plans[backend].report.total_cents == pytest.approx(
            plans["numpy"].report.total_cents, rel=1e-4)


def test_access_prediction_f1():
    w = generate_workload(n_datasets=150, n_months=24, seed=0)
    table = azure_table()
    clf, rep = train_tier_predictor(w, table, train_month=12, horizon=4)
    assert rep.f1 > 0.8, f"F1 too low: {rep.f1}, confusion={rep.confusion}"
    assert rep.confusion.sum() == 150


def test_predicted_vs_known_cost_gap():
    """Paper Table IV: predicted-access benefit ~= known-access benefit."""
    w = generate_workload(n_datasets=120, n_months=24, seed=1)
    table = azure_table()
    clf, _ = train_tier_predictor(w, table, train_month=12, horizon=4)
    known = optimal_tiers(w, table, 16, 20, tiers=(1, 2))
    pred = predicted_tiers(clf, w, 16, tiers=(1, 2))
    spans = np.array([d.size_gb for d in w.datasets])
    rho = w.reads_in(16, 20)

    def cost_of(tiers):
        sc = spans * table.storage_cents_gb_month[tiers] * 4
        rc = rho * spans * table.read_cents_gb[tiers]
        return (sc + rc).sum()

    c_known, c_pred = cost_of(known), cost_of(pred)
    all_hot = cost_of(np.ones(len(spans), int))
    benefit_known = 1 - c_known / all_hot
    benefit_pred = 1 - c_pred / all_hot
    assert benefit_known >= benefit_pred - 1e-9
    assert benefit_pred > 0.5 * benefit_known
