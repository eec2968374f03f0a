"""StreamingEngine: rolling-window ingest → compact → reoptimize lifecycle,
tier-state carry-over by file-set identity, and TieredStore.sync_plan.
"""

import numpy as np
import pytest

from repro.core.costs import azure_table
from repro.core.engine import ScopeConfig, StreamingEngine, compredict_rd_fn
from repro.data import workloads as wl
from repro.storage.store import TieredStore


def _engine(**kw):
    cfg = ScopeConfig(use_compression=False, months=1.0)
    sizes = {f"d{i}/{j}": 0.5 + 0.1 * j for i in range(6) for j in range(4)}
    return StreamingEngine(azure_table(), cfg, sizes, s_thresh=5.0, **kw), sizes


def _hot_cold_batch(hot=400.0, cold=0.01):
    """Two datasets with wildly different traffic — forces distinct tiers."""
    return [
        (("d0/0", "d0/1"), hot),
        (("d1/0", "d1/1", "d1/2"), cold),
    ]


def test_first_batch_places_everything_as_new():
    eng, _ = _engine()
    mig = eng.ingest_and_reoptimize(_hot_cold_batch())
    assert (mig.old_tier == -1).all()
    assert mig.n_moved == 0 and mig.migration_cents == 0.0
    assert mig.penalty_cents == 0.0
    r = eng.history[-1]
    assert r.n_new == r.n_partitions == 2
    # hot data lands on a faster tier than cold data
    tiers = {tuple(sorted(p.files)): int(t) for p, t in
             zip(mig.plan.problem.partitions, mig.plan.assignment.tier)}
    assert tiers[("d0/0", "d0/1")] < tiers[("d1/0", "d1/1", "d1/2")]


def test_steady_stream_is_idempotent():
    """window=1 makes repeated identical batches a no-drift stream: after
    the first placement no partition ever moves and nothing is charged."""
    eng, _ = _engine(window=1, drift_threshold=np.inf)
    eng.ingest_and_reoptimize(_hot_cold_batch())
    for _ in range(3):
        mig = eng.ingest_and_reoptimize(_hot_cold_batch())
        assert mig.n_moved == 0
        assert mig.migration_cents == 0.0 and mig.penalty_cents == 0.0
        assert (mig.new_tier == mig.old_tier).all()


def test_drift_triggers_bounded_migration_and_state_carry():
    """Cold->hot drift moves exactly the drifted partition; its survivor
    keeps tier identity across the fold."""
    eng, _ = _engine(window=1, drift_threshold=np.inf)
    mig0 = eng.ingest_and_reoptimize(_hot_cold_batch())
    cold_files = frozenset({"d1/0", "d1/1", "d1/2"})
    # same structure, cold dataset turns hot
    mig = eng.ingest_and_reoptimize(_hot_cold_batch(hot=400.0, cold=500.0))
    idx = [i for i, p in enumerate(mig.plan.problem.partitions)
           if p.files == cold_files]
    assert len(idx) == 1
    i = idx[0]
    assert mig.old_tier[i] >= 0, "survivor must carry its placement state"
    assert mig.moved[i] and mig.new_tier[i] < mig.old_tier[i]
    assert mig.migration_cents > 0.0
    # the untouched hot partition did not move
    other = [i2 for i2 in range(len(mig.moved)) if i2 != i]
    assert not mig.moved[other].any()
    assert mig0.plan.problem.n == mig.plan.problem.n


def test_migration_charged_once_then_stable():
    """After paying for a drift-induced move, re-ingesting the same rates
    charges nothing further (hysteresis at the stream level)."""
    eng, _ = _engine(window=1, drift_threshold=np.inf)
    eng.ingest_and_reoptimize(_hot_cold_batch())
    drifted = _hot_cold_batch(hot=400.0, cold=500.0)
    mig1 = eng.ingest_and_reoptimize(drifted)
    assert mig1.n_moved >= 1
    for _ in range(2):
        mig = eng.ingest_and_reoptimize(drifted)
        assert mig.n_moved == 0
        assert mig.migration_cents == 0.0 and mig.penalty_cents == 0.0


def test_minimum_stay_clock_carries_across_batches():
    """months accumulate for unmoved partitions, so early-deletion pricing
    sees the true residency, not per-batch resets."""
    eng, _ = _engine(window=1, drift_threshold=np.inf)
    eng.ingest_and_reoptimize(_hot_cold_batch(), months=1.0)
    eng.ingest_and_reoptimize(_hot_cold_batch(), months=1.0)
    held = {tuple(sorted(k)): sts[0].months_held
            for k, sts in eng._held.items()}
    assert held[("d0/0", "d0/1")] == pytest.approx(1.0)
    eng.ingest_and_reoptimize(_hot_cold_batch(), months=2.5)
    held = {tuple(sorted(k)): sts[0].months_held
            for k, sts in eng._held.items()}
    assert held[("d0/0", "d0/1")] == pytest.approx(3.5)


def test_enterprise_trace_end_to_end_with_store_sync():
    """Month-by-month enterprise trace through StreamingEngine, mirrored
    into a metered TieredStore via sync_plan."""
    w = wl.generate_workload(n_datasets=40, n_months=6, seed=5)
    rng = np.random.default_rng(1)
    sizes = wl.dataset_file_sizes(w)
    cfg = ScopeConfig(use_compression=False, months=1.0)
    eng = StreamingEngine(azure_table(), cfg, sizes, drift_threshold=0.5)
    store = TieredStore(azure_table())
    for batch in wl.stream_query_log(w, rng):
        if not batch:
            continue
        mig = eng.ingest_and_reoptimize(batch, months=1.0)
        parts = mig.plan.problem.partitions
        payloads = [b"x" * max(int(p.span * 1e3), 1) for p in parts]
        stats = store.sync_plan(mig.plan, payloads=payloads)
        # store ends the month holding exactly the plan's partitions
        assert len(store.keys()) == len(parts)
        for n, key in enumerate(store.plan_keys(mig.plan)):
            assert store.tier_of(key) == int(mig.plan.assignment.tier[n])
        # sync touches only what the migration plan says moved
        assert stats["moved"] + stats["reencoded"] >= mig.n_moved - \
            stats["deleted"] - stats["put"]
        store.advance_months(1.0)
    assert eng.history and eng.history[-1].n_partitions > 0
    assert store.meter.total_cents > 0.0


def test_empty_batches_are_noop_and_do_not_freeze_s_thresh():
    """An empty first batch must neither crash nor lock in a degenerate
    span cap; the first real batch still sizes s_thresh from its medians."""
    eng, _ = _engine()
    eng._s_thresh = None                    # force batch-derived sizing
    mig = eng.ingest_and_reoptimize([])
    assert mig.plan.problem.n == 0 and mig.n_moved == 0
    assert eng.partitioner is None          # creation deferred
    assert eng.history[-1].n_partitions == 0
    mig = eng.ingest_and_reoptimize(_hot_cold_batch())
    assert mig.plan.problem.n == 2
    assert np.isfinite(eng.partitioner.s_thresh)


# fixed decompression-speed labels (sec/GB) for the fitted predictor:
# the real `measure` times actual decompress calls, so the fit — and the
# scheme choice downstream of it — wobbles with wall-clock noise.  These
# tests assert backend parity and that compression engages, neither of
# which should depend on how loaded the CI host is.  Ratios stay real.
_DET_DSPEED = {"zstd-3": 1.0, "zlib-1": 3.0, "zlib-6": 4.0}


def _compredict_stream_fixture():
    """Small TPC-H stream with a fitted predictor wired in via rd_fn."""
    from repro.core import compredict as cp_mod
    from repro.core.compredict import CompressionPredictor, query_samples
    from repro.data import tpch
    from repro.storage.codecs import (CodecMeasurement, available_schemes,
                                      codec_by_name)

    db = tpch.generate(scale_rows=600, seed=9)
    queries = tpch.generate_queries(db, n_per_template=2, seed=10)
    parts, file_rows = tpch.partitions_from_queries(db, queries)
    schemes = available_schemes(("none", "zstd-3", "zlib-6", "zlib-1"))

    real_measure = cp_mod.measure

    def det_measure(codec, raw, repeats=1):
        m = real_measure(codec, raw, repeats=repeats)
        return CodecMeasurement(
            ratio=m.ratio, compress_sec=0.0,
            decompress_sec_per_gb=_DET_DSPEED.get(codec.name, 0.0))

    cp_mod.measure = det_measure
    try:
        pred = CompressionPredictor(model_name="SVR").fit(
            query_samples(queries, db.tables, max_rows=250)[:30],
            layouts=("col",),
            codecs=[codec_by_name(s) for s in schemes if s != "none"])
    finally:
        cp_mod.measure = real_measure
    sizes = {f: file_rows[f][0].select(file_rows[f][1]).nbytes("col") / 1e9
             for p in parts for f in p.files}
    batches = [[(tuple(sorted(p.files)), p.rho) for p in parts[:4]],
               [(tuple(sorted(p.files)), p.rho * (3.0 if i % 2 else 1.0))
                for i, p in enumerate(parts[:6])]]
    return pred, file_rows, sizes, schemes, batches


def test_streaming_feature_backend_parity():
    """Streaming re-prediction through compredict_rd_fn: the interpreted
    Pallas and NumPy feature backends yield the identical per-batch placement."""
    pred, file_rows, sizes, schemes, batches = _compredict_stream_fixture()
    migs = {}
    for backend in ("numpy", "interpret"):
        cfg = ScopeConfig(months=1.0, schemes=schemes)
        eng = StreamingEngine(
            azure_table(), cfg, sizes, s_thresh=5.0,
            rd_fn=compredict_rd_fn(pred, file_rows, layout="col",
                                   feature_backend=backend))
        migs[backend] = [eng.ingest_and_reoptimize(b, months=1.0)
                        for b in batches]
    for m_np, m_pal in zip(migs["numpy"], migs["interpret"]):
        np.testing.assert_array_equal(m_pal.plan.assignment.tier,
                                      m_np.plan.assignment.tier)
        np.testing.assert_array_equal(m_pal.plan.assignment.scheme,
                                      m_np.plan.assignment.scheme)
        assert m_pal.plan.report.total_cents == pytest.approx(
            m_np.plan.report.total_cents, rel=1e-4)
    # compression actually engages on the stream (schemes beyond 'none')
    assert (migs["numpy"][-1].plan.assignment.scheme > 0).any()


def test_compredict_rd_fn_caches_surviving_partitions(monkeypatch):
    """Partitions that survive across batches must not be re-materialized
    or re-serialized by compredict_rd_fn (hot-path cost)."""
    from repro.core import engine as eng_mod
    pred, file_rows, sizes, schemes, batches = _compredict_stream_fixture()
    calls = []
    real = eng_mod.PartitionStage._partition_tables

    def spy(parts, fr):
        calls.append(len(parts))
        return real(parts, fr)

    monkeypatch.setattr(eng_mod.PartitionStage, "_partition_tables",
                        staticmethod(spy))
    cfg = ScopeConfig(months=1.0, schemes=schemes)
    eng = StreamingEngine(azure_table(), cfg, sizes, s_thresh=5.0,
                          window=1, drift_threshold=np.inf,
                          rd_fn=compredict_rd_fn(pred, file_rows))
    eng.ingest_and_reoptimize(batches[0], months=1.0)
    assert len(calls) == 1 and calls[0] > 0  # first batch: all materialized
    eng.ingest_and_reoptimize(batches[0], months=1.0)
    assert len(calls) == 1                   # identical batch: pure cache hit


def _two_provider_table():
    """Hand-built 2-provider space where hot data belongs on provider A
    (cheap reads) and cold data on provider B (cheap storage), with real
    egress — forces a provider move on a hot->cold drift."""
    from repro.core.costs import ProviderCostTable, CostTable, \
        multi_cloud_table

    def one_tier(storage, read, egress):
        return ProviderCostTable(
            provider=f"p{storage}", egress_out_cents_gb=egress,
            table=CostTable(
                storage_cents_gb_month=np.array([storage]),
                read_cents_gb=np.array([read]),
                write_cents_gb=np.array([0.01]),
                ttfb_seconds=np.array([0.02]),
                capacity_gb=np.array([np.inf]),
                early_delete_months=np.array([0.0]),
                names=("only",)))
    return multi_cloud_table([one_tier(10.0, 0.01, 0.5),
                              one_tier(1.0, 5.0, 0.5)])


def test_empty_batch_after_provider_move_reports_zero_egress():
    """Regression (ISSUE 5): the empty-stream step must construct the same
    StreamStepReport / MigrationPlan field set as the live path — in
    particular an explicit ``egress_cents == 0.0`` right after a provider
    move, not a missing/defaulted field."""
    import dataclasses
    table = _two_provider_table()
    cfg = ScopeConfig(use_compression=False, months=1.0)
    sizes = {"d0/0": 1.0, "d0/1": 1.0}
    eng = StreamingEngine(table, cfg, sizes, s_thresh=5.0, window=1,
                          drift_threshold=0.5)
    eng.ingest_and_reoptimize([(("d0/0", "d0/1"), 100.0)])
    mig = eng.ingest_and_reoptimize([(("d0/0", "d0/1"), 0.001)])
    assert mig.n_moved == 1 and mig.egress_cents > 0.0  # provider move paid
    live = eng.history[-1]
    # empty batches expire the window; compaction drops the dead partition
    eng.ingest_and_reoptimize([])
    empty_mig = eng.ingest_and_reoptimize([])
    assert empty_mig.plan.problem.n == 0
    rep = eng.history[-1]
    assert rep.n_partitions == 0
    assert rep.egress_cents == 0.0 and rep.migration_cents == 0.0
    # field-set parity with the live path (no defaulted/missing fields)
    assert set(dataclasses.asdict(rep)) == set(dataclasses.asdict(live))
    # the empty MigrationPlan carries the live path's arrays too
    for arr in (empty_mig.candidate, empty_mig.move_transfer_cents,
                empty_mig.move_egress_cents, empty_mig.move_penalty_cents,
                empty_mig.old_stored_gb):
        assert arr is not None and arr.shape == (0,)
    assert empty_mig.select(np.zeros(0, bool)) is empty_mig


def test_select_moves_defers_and_reproposes_next_batch():
    """A partial step keeps deferred candidates at their old placement,
    charges nothing for them, and re-proposes them next batch."""
    eng, _ = _engine(window=1, drift_threshold=np.inf)
    eng.ingest_and_reoptimize(_hot_cold_batch())
    drifted = _hot_cold_batch(hot=400.0, cold=500.0)
    mig = eng.ingest_and_reoptimize(
        drifted, select_moves=lambda m: np.zeros(m.plan.problem.n, bool))
    assert mig.n_candidates >= 1 and mig.n_moved == 0
    assert mig.migration_cents == 0.0 and mig.penalty_cents == 0.0
    assert np.array_equal(mig.new_tier, mig.old_tier)
    assert eng.history[-1].n_deferred == mig.n_candidates
    # deferred moves stay drifted (lock base kept) and execute next batch
    mig2 = eng.ingest_and_reoptimize(drifted)
    assert mig2.n_moved == mig.n_candidates
    assert eng.history[-1].n_deferred == 0


def test_stream_rho_abs_tol_stabilizes_cold_lock():
    """Epsilon accesses on a cold partition must not reset its drift-lock
    base when the absolute floor is set; without the floor every epsilon
    batch re-bases the lock (the scheme lock is defeated)."""
    cfg = ScopeConfig(use_compression=False, months=1.0)
    sizes = {f"d{i}/{j}": 0.5 + 0.1 * j for i in range(6) for j in range(4)}
    cold_files = frozenset({"d1/0", "d1/1", "d1/2"})

    def run(abs_tol):
        eng = StreamingEngine(azure_table(), cfg, sizes, s_thresh=5.0,
                              window=1, drift_threshold=np.inf,
                              rho_abs_tol=abs_tol)
        eng.ingest_and_reoptimize(_hot_cold_batch(cold=0.0))
        refs = []
        for eps in (1e-6, 3e-6, 2e-6):
            eng.ingest_and_reoptimize(_hot_cold_batch(cold=eps))
            refs.append(eng._held[cold_files][0].rho_ref)
        return refs

    # floor on: the lock base never re-bases off the original cold rate
    assert run(0.5) == [0.0, 0.0, 0.0]
    # floor off: every epsilon batch counts as drift and re-bases the lock
    assert all(r > 0.0 for r in run(0.0))


def test_sync_plan_requires_partitions_and_payloads():
    eng, _ = _engine()
    mig = eng.ingest_and_reoptimize(_hot_cold_batch())
    store = TieredStore(azure_table())
    with pytest.raises(ValueError):
        store.sync_plan(mig.plan)           # no raw_bytes, no payloads
    import dataclasses
    bad = dataclasses.replace(mig.plan.problem, partitions=None)
    with pytest.raises(ValueError):
        store.sync_plan(dataclasses.replace(mig.plan, problem=bad))


def test_sync_plan_preserves_foreign_objects():
    """sync_plan only reconciles gpart-* objects; checkpoints and manual
    puts survive."""
    eng, _ = _engine()
    mig = eng.ingest_and_reoptimize(_hot_cold_batch())
    store = TieredStore(azure_table())
    store.put("ckpt-0001", b"model", tier=1)
    parts = mig.plan.problem.partitions
    store.sync_plan(mig.plan,
                    payloads=[b"x" * 100 for _ in parts])
    assert "ckpt-0001" in store.keys()
