"""Unit tests for the HLO analyzer + cost-model invariants (hypothesis)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import hlo_stats
from repro.core.costs import Weights, azure_table, cost_tensor, latency_feasible

MINI_HLO = """\
HloModule test

%cond.1 (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant({...})
  %y = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16] all-reduce(%y), channel_id=1, replica_groups=[2,4]<=[8]
  %one = s32[] constant(1)
  %j = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%j, %ar)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%zero, %a)
  %loop = (s32[], f32[8,16]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,16] get-tuple-element(%loop), index=1
}
"""


def test_hlo_stats_while_trip_multiplication():
    st_ = hlo_stats.analyze(MINI_HLO)
    # dot: 2 * 8*16 * 16 flops, executed 12 times
    assert st_.flops == pytest.approx(12 * 2 * 8 * 16 * 16)
    # all-reduce operand bytes: 8*16*4 per trip, 12 trips
    assert st_.coll_bytes == pytest.approx(12 * 8 * 16 * 4)
    assert st_.coll_by_kind["all-reduce"] == st_.coll_bytes
    assert st_.n_collectives == 12


def test_hlo_stats_group_size_parsing():
    assert hlo_stats._group_size("replica_groups=[2,4]<=[8]") == 4
    assert hlo_stats._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4
    assert hlo_stats._group_size("no groups here") == 1


def test_hlo_stats_trip_count_fusion_wrapped():
    text = MINI_HLO.replace(
        "ROOT %lt = pred[] compare(%i, %c), direction=LT",
        "ROOT %lt = pred[] fusion(%i, %c), kind=kLoop, calls=%wc")
    st_ = hlo_stats.analyze(text)
    assert st_.flops == pytest.approx(12 * 2 * 8 * 16 * 16)


# ------------------------------------------------------ cost-model invariants
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000))
def test_better_compression_never_costs_more(seed):
    """For a fixed tier, raising R (same D) weakly decreases cost."""
    rng = np.random.default_rng(seed)
    table = azure_table()
    N = 4
    spans = rng.uniform(0.1, 100, N)
    rho = rng.gamma(1.0, 10.0, N)
    cur = np.full(N, -1)
    R1 = rng.uniform(1.0, 4.0, (N, 1))
    R2 = R1 * rng.uniform(1.0, 2.0, (N, 1))      # strictly better ratios
    D = rng.uniform(0.0, 1.0, (N, 1))
    c1 = cost_tensor(spans, rho, cur, R1, D, table, Weights())
    c2 = cost_tensor(spans, rho, cur, R2, D, table, Weights())
    assert (c2 <= c1 + 1e-9).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000))
def test_latency_feasibility_monotone_in_threshold(seed):
    rng = np.random.default_rng(seed)
    table = azure_table()
    D = rng.uniform(0, 5, (3, 2))
    t_lo = rng.uniform(0, 2, 3)
    f_lo = latency_feasible(D, t_lo, table)
    f_hi = latency_feasible(D, t_lo + rng.uniform(0, 5, 3), table)
    assert (f_lo <= f_hi).all()                  # relaxing T never removes


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_pushdown_fraction_reduces_access_cost(seed):
    """Paper §IV-A: pushdown-amenable queries drop read+decomp terms."""
    rng = np.random.default_rng(seed)
    table = azure_table()
    N = 3
    spans = rng.uniform(0.1, 50, N)
    rho = rng.gamma(1.0, 10.0, N) + 1.0
    cur = np.full(N, -1)
    R = rng.uniform(1.0, 4.0, (N, 2))
    D = rng.uniform(0.01, 2.0, (N, 2))
    c0 = cost_tensor(spans, rho, cur, R, D, table, pushdown_fraction=0.0)
    c5 = cost_tensor(spans, rho, cur, R, D, table, pushdown_fraction=0.5)
    c1 = cost_tensor(spans, rho, cur, R, D, table, pushdown_fraction=1.0)
    assert (c5 <= c0 + 1e-9).all() and (c1 <= c5 + 1e-9).all()
