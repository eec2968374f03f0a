"""The program's host spans (``repro.core.tracing``): which a plan opens,
how they nest, that each opens once per plan whatever the fleet's size,
and that a real profiler trace holds them on its host plane."""

import contextlib

import jax
import numpy as np
import pytest

from repro.core import tracing
from repro.core.compredict import CompressionPredictor, query_samples
from repro.core.costs import azure_table
from repro.core.engine import PlacementEngine, PlacementProblem, ScopeConfig
from repro.core.fleet import FleetEngine
from repro.data import tpch
from repro.storage.codecs import available_schemes, codec_by_name

# (span, the span it opens inside), in the order a plan opens them
LAKE = [("plan", None), ("partition", "plan"), ("gpart", "partition"),
        ("partition.materialize", "partition"), ("compress", "plan"),
        ("features.encode", "compress"),
        ("features.encode.render", "features.encode"),
        ("features.entropy", "compress"),
        ("assign", "plan"), ("billing", "plan")]
FLEET_UNCAPPED = [("fleet.plan", None), ("assign", "fleet.plan"),
                  ("assign.inputs", "assign"), ("billing", "fleet.plan")]
FLEET_CAPPED = FLEET_UNCAPPED[:3] + [("assign.scan", "assign"),
                                     ("assign.finish", "assign"),
                                     ("billing", "fleet.plan")]
# one quota shared by the fleet: the finish runs its three passes
FLEET_SHARED = FLEET_CAPPED[:5] + [("assign.repair", "assign.finish"),
                                   ("assign.shared_repair", "assign.finish"),
                                   ("assign.polish", "assign.finish"),
                                   ("billing", "fleet.plan")]
SCHEMES = ("none", "lz4", "zstd3")


class _Opened(list):
    """(name, enclosing span) of every span opened; ``args[name]`` holds
    the keyword arguments of the last opening of ``name`` and the metadata
    set on it before it closed."""

    def __init__(self):
        super().__init__()
        self.args = {}


class _Span:
    """What a recorded span yields: ``set_metadata`` adds to its args."""

    def __init__(self, args: dict):
        self.args = args

    def set_metadata(self, **kw):
        self.args.update(kw)


@pytest.fixture
def opened(monkeypatch):
    """Every span opened while the test runs, as (name, enclosing span)."""
    seen, stack = _Opened(), []

    @contextlib.contextmanager
    def record(name, **args):
        seen.append((name, stack[-1] if stack else None))
        seen.args[name] = dict(args)
        stack.append(name)
        try:
            yield _Span(seen.args[name])
        finally:
            stack.pop()

    monkeypatch.setattr(tracing, "span", record)
    return seen


@pytest.fixture(scope="module")
def lake():
    """A small TPC-H lake planned with the device backends."""
    db = tpch.generate(scale_rows=500, seed=7)
    qs = tpch.generate_queries(db, n_per_template=2, seed=8)
    parts, file_rows = tpch.partitions_from_queries(db, qs)
    scheme = available_schemes(("zstd-3", "zlib-6", "zlib-1"))[0]
    pred = CompressionPredictor(model_name="SVR").fit(
        query_samples(qs, db.tables, max_rows=200)[:16], layouts=("col",),
        codecs=[codec_by_name(scheme)])
    cfg = ScopeConfig(schemes=("none", scheme), predictor=pred,
                      partition_backend="jnp", feature_backend="jnp",
                      tier_whitelist=(0, 1, 2))
    return PlacementEngine(azure_table(), cfg), parts, file_rows


def _fleet(T: int, capped: bool, seed: int = 0):
    """A fleet engine and T tenants; the capped fleet's tier caps bind."""
    table = azure_table()
    caps = np.array([np.inf, np.inf, 10.0, np.inf]) if capped else None
    cfg = ScopeConfig(schemes=SCHEMES, capacity_gb=caps)
    rng = np.random.default_rng(seed)
    probs = []
    for n in rng.integers(3, 10, T):
        R = np.concatenate([np.ones((n, 1)), rng.uniform(1.2, 6.0, (n, 2))],
                           1)
        D = np.concatenate([np.zeros((n, 1)), rng.uniform(0.01, 3.0, (n, 2))],
                           1)
        probs.append(PlacementProblem(
            spans_gb=rng.uniform(0.5, 50.0, n), rho=rng.gamma(1.0, 20.0, n),
            current_tier=np.full(n, -1), R=R, D=D, schemes=list(SCHEMES),
            table=table, cfg=cfg))
    return FleetEngine(table, cfg), probs


def _shared_fleet(T: int, seed: int = 0):
    """The uncapped fleet under one cool-tier quota that all T tenants
    share, at half their unconstrained use of the tier: it binds."""
    fe, probs = _fleet(T, capped=False, seed=seed)
    quota = np.full(4, np.inf)
    free = FleetEngine(fe.table, fe.cfg, shared_tier_groups=np.arange(4),
                       shared_capacity_gb=quota.copy())
    quota[2] = 0.5 * free.assign_batch(probs).shared_use_gb[2]
    return FleetEngine(fe.table, fe.cfg, shared_tier_groups=np.arange(4),
                       shared_capacity_gb=quota), probs


def test_lake_plan_opens_each_span_once_nested(opened, lake):
    eng, parts, file_rows = lake
    eng.run(parts, file_rows)
    assert opened == LAKE
    eng.run(parts, file_rows)
    assert opened == LAKE + LAKE


@pytest.mark.parametrize("capped,expected", [(False, FLEET_UNCAPPED),
                                             (True, FLEET_CAPPED)])
def test_fleet_plan_opens_each_span_once_nested(opened, capped, expected):
    fe, probs = _fleet(6, capped)
    plan = fe.solve(probs)
    assert opened == expected
    assert plan.fleet.feasible


@pytest.mark.parametrize("capped", [False, True])
def test_fleet_spans_do_not_grow_with_tenants(opened, capped):
    for T in (4, 64):
        fe, probs = _fleet(T, capped, seed=T)
        opened.clear()
        fe.solve(probs)
        assert opened == (FLEET_CAPPED if capped else FLEET_UNCAPPED), T


@pytest.mark.parametrize("T", [4, 64])
def test_shared_quota_plan_opens_the_finish_passes_once(opened, T):
    fe, probs = _shared_fleet(T, seed=T)
    opened.clear()
    plan = fe.solve(probs)
    assert opened == FLEET_SHARED
    assert plan.fleet.feasible
    datasets = sum(p.n for p in probs)
    for name in ("assign.repair", "assign.shared_repair", "assign.polish"):
        args = opened.args[name]
        assert args["tenants"] == T and args["datasets"] == datasets
        assert 1 <= args["candidates"] <= 16
    # each pass sees the candidates the one before it let through
    assert (opened.args["assign.repair"]["candidates"]
            >= opened.args["assign.shared_repair"]["candidates"]
            >= opened.args["assign.polish"]["candidates"])
    # the polish counts its moves when it closes; the other passes do not
    moves = opened.args["assign.polish"]["moves"]
    assert isinstance(moves, int) and moves >= 0
    assert "moves" not in opened.args["assign.repair"]


def _host_spans(path):
    """(name, start, end, line) of every ``scope:`` event, and the names
    of the planes that hold one."""
    data = jax.profiler.ProfileData.from_file(str(path))
    spans, planes = [], set()
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    planes.add(plane.name)
                    spans.append((e.name[len(tracing.PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  (plane.name, line.name)))
    return spans, planes


def _enclosing(spans):
    """Each span with the innermost span of its thread that encloses it."""
    out = []
    for name, a, b, line in sorted(spans, key=lambda s: (s[1], -s[2])):
        outer = [s for s in spans if s[3] == line and s[1] <= a
                 and b <= s[2] and s[:3] != (name, a, b)]
        parent = (max(outer, key=lambda s: (s[1], -s[2]))[0] if outer
                  else None)
        out.append((name, parent))
    return out


def test_profiler_trace_holds_the_spans_on_the_host_plane(lake, tmp_path):
    eng, parts, file_rows = lake
    fe, probs = _fleet(6, capped=True)
    eng.run(parts, file_rows)         # compile outside the trace
    fe.solve(probs)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(parts, file_rows)
        fe.solve(probs)
    path, = tmp_path.glob("**/*.xplane.pb")
    spans, planes = _host_spans(path)
    assert planes and all(p.startswith("/host:") for p in planes)
    assert _enclosing(spans) == LAKE + FLEET_CAPPED
    args, = [dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name == tracing.PREFIX + "features.encode.render"]
    assert 0 < args["rendered"] <= args["values"]
