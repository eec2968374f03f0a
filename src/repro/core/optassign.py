"""OPTASSIGN — optimal tier + compression-scheme assignment (paper §IV).

Solvers
-------
``greedy_assign``       exact for unbounded capacities (Thm 3), O(NLK); the
                        vectorized JAX version is the PB-scale production path.
``matching_assign``     exact for equal-size/no-compression with capacities
                        (Thm 2) via min-cost flow == min-weight bipartite
                        matching on tier copies.
``capacitated_assign``  general capacitated case (strongly NP-hard, Thm 1):
                        vectorized JAX Lagrangian dual ascent (jitted scan over
                        all N*L*K cells) + argsort-based greedy repair +
                        delta-matrix 1-swap local search; validated against
                        ``brute_force`` in tests.
``capacitated_assign_batch``  the fleet path: T ragged tenant problems padded
                        into one (T, N_max, L, K) batch and solved by a single
                        batched (optionally ``shard_map``-sharded) Lagrangian
                        scan dispatch. Bit-identical per tenant to
                        ``capacitated_assign`` when no *shared* (fleet-wide)
                        capacity rows couple the tenants.
``greedy_assign_batch``  batched unbounded path, one dispatch for T tenants.
``capacitated_assign_ref``  the original pure-Python solver, kept as the
                        correctness reference for the vectorized path.
``brute_force``         exact enumeration oracle for tiny instances.

All solvers consume the (N,L,K) cost tensor and (N,L,K) feasibility mask from
:mod:`repro.core.costs`, so objective-weight variants (alpha/beta/gamma,
pushdown fraction, scheme locking for existing partitions) are handled
uniformly upstream.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing

BIG = 1e18


@dataclasses.dataclass
class Assignment:
    tier: np.ndarray       # (N,) int
    scheme: np.ndarray     # (N,) int
    cost: float            # objective value of chosen cells
    feasible: bool         # capacity + latency respected


def _masked(cost: np.ndarray, feasible: np.ndarray) -> np.ndarray:
    return np.where(feasible, cost, BIG)


def lock_schemes(feasible: np.ndarray, locked_scheme: np.ndarray) -> np.ndarray:
    """Paper's last ILP constraint: existing partitions keep their scheme.

    ``locked_scheme[n] == -1`` means partition n is new (free choice).
    """
    K = feasible.shape[2]
    locked = np.asarray(locked_scheme).astype(int)
    keep = (locked[:, None] < 0) | (np.arange(K)[None, :] == locked[:, None])
    return feasible & keep[:, None, :]


# --------------------------------------------------------------------- greedy
@partial(jax.jit, static_argnames=())
def _greedy_jax(cost: jnp.ndarray, feasible: jnp.ndarray):
    masked = jnp.where(feasible, cost, BIG)
    flat = masked.reshape(masked.shape[0], -1)
    idx = jnp.argmin(flat, axis=1)
    best = jnp.take_along_axis(flat, idx[:, None], axis=1)[:, 0]
    K = masked.shape[2]
    return idx // K, idx % K, best


def greedy_assign(cost: np.ndarray, feasible: np.ndarray) -> Assignment:
    """Exact when capacities are unbounded (Thm 3). O(NLK)."""
    if cost.shape[0] == 0:
        z = np.zeros(0, np.int64)
        return Assignment(z, z.copy(), 0.0, True)
    tier, scheme, best = map(np.asarray, _greedy_jax(jnp.asarray(cost),
                                                     jnp.asarray(feasible)))
    tier, scheme = tier.astype(int), scheme.astype(int)
    ok = bool((best < BIG).all())
    # argmin runs in f32 on device; re-total the objective in f64 for exactness
    n = np.arange(cost.shape[0])
    total = float(np.asarray(cost, np.float64)[n, tier, scheme].sum()) if ok \
        else float("inf")
    return Assignment(tier, scheme, total, ok)


# ------------------------------------------------------------------- matching
class _MCMF:
    """Successive-shortest-path min-cost max-flow (SPFA variant). Exact."""

    def __init__(self, n: int):
        self.n = n
        self.to: list[int] = []
        self.cap: list[float] = []
        self.cost: list[float] = []
        self.head: list[list[int]] = [[] for _ in range(n)]

    def add(self, u: int, v: int, cap: float, cost: float) -> None:
        self.head[u].append(len(self.to)); self.to.append(v)
        self.cap.append(cap); self.cost.append(cost)
        self.head[v].append(len(self.to)); self.to.append(u)
        self.cap.append(0.0); self.cost.append(-cost)

    def run(self, s: int, t: int):
        flow = cost = 0.0
        INF = float("inf")
        while True:
            dist = [INF] * self.n
            in_q = [False] * self.n
            prev_e = [-1] * self.n
            dist[s] = 0.0
            queue = collections.deque([s])
            in_q[s] = True
            while queue:
                u = queue.popleft()
                in_q[u] = False
                for e in self.head[u]:
                    if self.cap[e] > 1e-12 and dist[u] + self.cost[e] < dist[self.to[e]] - 1e-12:
                        dist[self.to[e]] = dist[u] + self.cost[e]
                        prev_e[self.to[e]] = e
                        if not in_q[self.to[e]]:
                            queue.append(self.to[e])
                            in_q[self.to[e]] = True
            if dist[t] == INF:
                return flow, cost
            # bottleneck
            push, v = INF, t
            while v != s:
                e = prev_e[v]
                push = min(push, self.cap[e])
                v = self.to[e ^ 1]
            v = t
            while v != s:
                e = prev_e[v]
                self.cap[e] -= push
                self.cap[e ^ 1] += push
                v = self.to[e ^ 1]
            flow += push
            cost += push * dist[t]


def matching_assign(cost_nl: np.ndarray, feasible_nl: np.ndarray,
                    capacity_units: np.ndarray) -> Assignment:
    """Equal-size partitions, no compression (Thm 2).

    Min-weight bipartite matching of N unit-size partitions onto Z_l tier
    copies; the tier-copy graph collapses to a transportation problem solved
    exactly by min-cost max-flow (source -> partition -> tier -> sink).
    """
    N, L = cost_nl.shape
    cost = _masked(cost_nl, feasible_nl)
    cap = np.minimum(capacity_units.astype(np.float64), N)
    S, T = N + L, N + L + 1
    g = _MCMF(N + L + 2)
    for n in range(N):
        g.add(S, n, 1.0, 0.0)
        for l in range(L):
            if cost[n, l] < BIG:
                g.add(n, N + l, 1.0, float(cost[n, l]))
    for l in range(L):
        g.add(N + l, T, float(cap[l]), 0.0)
    flow, total = g.run(S, T)
    if flow < N - 1e-9:
        return Assignment(np.full(N, -1), np.zeros(N, int), float("inf"), False)
    assign = np.full(N, -1, np.int64)
    for n in range(N):
        for e in g.head[n]:
            v = g.to[e]
            if N <= v < N + L and e % 2 == 0 and g.cap[e] < 0.5:
                assign[n] = v - N
    return Assignment(assign, np.zeros(N, int), float(total), True)


# ---------------------------------------------------------------- capacitated
def _chosen_usage(stored_gb: np.ndarray, tier: np.ndarray,
                  scheme: np.ndarray) -> np.ndarray:
    """Per-tier GB occupied by the chosen (tier, scheme) cells, shape (L,)."""
    use = np.zeros(stored_gb.shape[1])
    np.add.at(use, tier, stored_gb[np.arange(tier.shape[0]), tier, scheme])
    return use


def _constraint_rows(capacity_gb: np.ndarray,
                     tier_groups: Optional[np.ndarray],
                     group_capacity_gb: Optional[np.ndarray],
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Capacity constraints as a membership matrix ``A`` (C, L) + caps (C,).

    Rows 0..L-1 are the per-tier capacities (identity); optional group rows
    (e.g. per-provider totals over a block of flat tiers in the multi-cloud
    placement space) follow. A constraint is ``A[c] @ use <= cap_all[c]``.
    """
    if (tier_groups is None) != (group_capacity_gb is None):
        raise ValueError("tier_groups and group_capacity_gb must be "
                         "passed together")
    L = capacity_gb.shape[0]
    A = np.eye(L, dtype=bool)
    cap_all = np.asarray(capacity_gb, np.float64)
    if tier_groups is not None:
        g = np.asarray(tier_groups, int)
        gcap = np.asarray(group_capacity_gb, np.float64)
        G = gcap.shape[0]
        if g.min() < 0 or g.max() >= G:
            raise ValueError(f"tier_groups ids must lie in [0, {G}) to "
                             f"match group_capacity_gb")
        A = np.concatenate([A, np.arange(G)[:, None] == g[None, :]], 0)
        cap_all = np.concatenate([cap_all, gcap])
    return A, cap_all


@partial(jax.jit, static_argnames=("iters",))
def _lagrangian_scan(masked: jnp.ndarray, stored: jnp.ndarray,
                     cap: jnp.ndarray, finite_cap: jnp.ndarray,
                     group_of_tier: jnp.ndarray, gcap: jnp.ndarray,
                     finite_gcap: jnp.ndarray,
                     step0: jnp.ndarray, iters: int):
    """Jitted dual ascent over all N*L*K cells; one candidate per step.

    Dualizes both the per-tier capacities and the group (per-provider)
    capacities: a tier's effective multiplier is its own lambda plus its
    group's. With no groups the group lambdas stay exactly zero.
    """
    N, L, K = masked.shape
    G = gcap.shape[0]
    flat_cost = masked.reshape(N, -1)
    flat_stored = stored.reshape(N, -1)

    def body(lam, it):
        eff = lam[:L] + lam[L:][group_of_tier]
        adj = flat_cost + (eff[None, :, None] * stored).reshape(N, -1)
        idx = jnp.argmin(adj, axis=1)
        chosen = jnp.take_along_axis(flat_stored, idx[:, None], axis=1)[:, 0]
        use = jnp.zeros(L, masked.dtype).at[idx // K].add(chosen)
        use_g = jnp.zeros(G, masked.dtype).at[group_of_tier].add(use)
        grad = jnp.concatenate([jnp.where(finite_cap, use - cap, 0.0),
                                jnp.where(finite_gcap, use_g - gcap, 0.0)])
        lam = jnp.maximum(0.0, lam + step0 / (1.0 + it) * grad)
        return lam, idx

    _, cells = jax.lax.scan(body, jnp.zeros(L + G, masked.dtype),
                            jnp.arange(iters, dtype=masked.dtype))
    return cells                                    # (iters, N) flat indices


def _repair_vec(tier: np.ndarray, scheme: np.ndarray, masked: np.ndarray,
                stored: np.ndarray, A: np.ndarray, cap_all: np.ndarray,
                finite_all: np.ndarray) -> Optional[np.ndarray]:
    """Argsort-based greedy repair: evict cheapest-delta members of the most
    over-capacity constraint (a tier, or a group such as a provider) until
    every finite capacity is respected."""
    N, L, K = masked.shape
    use = _chosen_usage(stored, tier, scheme)
    Af = A & finite_all[:, None]                    # (C, L)
    A_f = A.astype(np.float64)
    for _ in range(4 * N + 8):
        # einsum, not @: the batched fleet precheck replicates this exact
        # ascending-l accumulation, so round-0 decisions agree bitwise
        use_c = np.einsum("cl,l->c", A_f, use)
        over = np.where(finite_all & (use_c > cap_all + 1e-9))[0]
        if over.size == 0:
            return use
        c = over[np.argmax((use_c - cap_all)[over])]
        in_c = A[c]                                 # (L,) tiers in constraint
        members = np.where(in_c[tier])[0]
        if members.size == 0:
            return None
        cur = masked[members, tier[members], scheme[members]]
        # per-tier room = tightest finite constraint containing that tier
        slack_c = np.where(finite_all, cap_all - use_c, np.inf)
        room = np.where(Af, slack_c[:, None], np.inf).min(0)         # (L,)
        ok = (masked[members] < BIG) & (stored[members]
                                        <= room[None, :, None] + 1e-9)
        ok[:, in_c, :] = False                      # must leave the constraint
        delta = np.where(ok, masked[members] - cur[:, None, None],
                         np.inf).reshape(members.size, -1)
        best_cell = delta.argmin(1)
        best_delta = delta[np.arange(members.size), best_cell]
        moved = False
        for m in np.argsort(best_delta):
            if use_c[c] <= cap_all[c] + 1e-9:
                break
            if not np.isfinite(best_delta[m]):
                break
            l2, k2 = divmod(int(best_cell[m]), K)
            n = int(members[m])
            room2 = np.where(Af[:, l2], cap_all - use_c, np.inf).min() \
                if Af[:, l2].any() else np.inf
            if stored[n, l2, k2] > room2 + 1e-9:
                continue             # room shrank this batch; retry next round
            l1 = tier[n]
            s1, s2 = stored[n, l1, scheme[n]], stored[n, l2, k2]
            use[l1] -= s1
            use[l2] += s2
            use_c += A[:, l2] * s2 - A[:, l1] * s1
            tier[n], scheme[n] = l2, k2
            moved = True
        if not moved:
            return None
    return None


@lru_cache(maxsize=64)
def _gates(a: bytes, fin: bytes, C: int, L: int, K: int):
    """What the 1-swap descent reads of its constraint rows ``A`` (C, L)
    and their finite mask, given as bytes: the tiers a finite row caps,
    their flat cells (ascending), ``A`` as floats, and the masks of the
    room tables. The same rows come back call after call (every candidate
    of a solve, every sweep over a tenant), so each is built once.

    ``room[n, l] = min(T1[tier[n], l], T2[tier[n], l] + stored_cur[n])``,
    T1 the least slack over the finite rows that hold l and not tier[n],
    T2 over those that hold both: the vacated bytes free room only in the
    rows that hold the row's own tier. Rounding is monotone, so adding
    them after the min gives the min of the per-row sums bit for bit.
    ``m_t[c, (i, l1, l)]`` selects the rows of T1 (i = 0) and T2 (i = 1).
    """
    A = np.frombuffer(a, bool).reshape(C, L)
    Af = A & np.frombuffer(fin, bool)[:, None]
    capped = Af.any(0)
    gated = np.flatnonzero(capped)
    gcells = np.flatnonzero(np.repeat(capped, K))
    m_t = (Af[:, None, None, gated]
           & (np.arange(2)[:, None] == A[:, None, :])[..., None])
    out = gated, gcells, A.astype(np.float64), m_t.reshape(C, -1)
    for x in out:                   # shared by every caller: read-only
        x.setflags(write=False)
    return out


def _local_search_vec(tier: np.ndarray, scheme: np.ndarray, use: np.ndarray,
                      masked: np.ndarray, stored: np.ndarray, A: np.ndarray,
                      cap_all: np.ndarray, finite_all: np.ndarray,
                      max_moves: Optional[int] = None) -> int:
    """Best-improvement 1-swap descent over the (N,L,K) delta matrix.

    Each step takes the cell of least ``masked - cur`` (first occurrence in
    the flattened grid) that fits the room its tier leaves in every finite
    constraint, and stops when no move gains more than 1e-12. A move
    changes one row of the delta matrix and the usage of two tiers, so the
    descent rescans only what a move can change: the matrix is kept across
    moves and only the moved row is rewritten; on tiers that no finite
    constraint covers each row's best cell is cached and recomputed only
    for the moved row; the cells of the capacity-gated tiers are rescanned
    every step, against a room built from an (L, L) table instead of an
    (N, C, L) reduction. The moves are those of a full rescan, in the same
    order. Returns the number of moves made.

    ``max_moves`` overrides the default ``8 * N + 64`` budget so the
    lockstep fleet descent can hand its tail rows over mid-trajectory
    with their remaining budget intact.
    """
    N, L, K = masked.shape
    budget = 8 * N + 64 if max_moves is None else max_moves
    if N == 0 or budget <= 0:
        return 0
    n_idx = np.arange(N)
    gated, gcells, A_f, m_t = _gates(
        A.astype(bool, copy=False).tobytes(),
        finite_all.astype(bool, copy=False).tobytes(), *A.shape, K)
    G = gated.size
    m2 = masked.reshape(N, L * K)
    cur = masked[n_idx, tier, scheme]
    stored_cur = stored[n_idx, tier, scheme]
    # bf[n, j]: the gain of moving n to flat cell j, inf where infeasible
    # or gated; the gated tiers' cells sit apart in base_g (N, G, K)
    feas = m2 < BIG
    bf = np.where(feas, m2 - cur[:, None], np.inf)
    # a move lands on a feasible cell, so the moved row's cur is finite
    # and m_inf - cur rewrites the row as the where above would
    m_inf = np.where(feas, m2, np.inf)
    if G:
        base_g = bf[:, gcells].reshape(N, G, K)
        bf[:, gcells] = np.inf
        stored_g = stored[:, gated]
    bf_idx = bf.argmin(1)
    bf_val = bf[n_idx, bf_idx]
    moves = 0
    for _ in range(budget):
        n = int(bf_val.argmin())                    # first row, free cells
        v, j = bf_val[n], int(bf_idx[n])
        if G:
            # einsum, not @: the lockstep fleet descent replicates this
            # exact ascending-l accumulation for bitwise-equal trajectories
            slack0 = cap_all - np.einsum("cl,l->c", A_f, use)
            t = np.where(m_t, slack0[:, None], np.inf).min(0).reshape(
                2, L, G).take(tier, 1)                         # (2, N, G)
            room = np.minimum(t[0], t[1] + stored_cur[:, None])
            dg = np.where(stored_g <= room[:, :, None] + 1e-9, base_g,
                          np.inf).reshape(N, G * K)
            ng, cg = divmod(int(dg.argmin()), G * K)  # first row, gated
            vg = dg[ng, cg]
            # the first row holding the least value moves; within it the
            # lower flat index wins a tie between a gated and a free cell
            if vg < v or (vg == v and (ng < n or (
                    ng == n and gcells[cg] < j))):
                n, v, j = ng, vg, int(gcells[cg])
        if not v < -1e-12:
            break
        l2, k2 = divmod(j, K)
        use[tier[n]] -= stored[n, tier[n], scheme[n]]
        use[l2] += stored[n, l2, k2]
        tier[n], scheme[n] = l2, k2
        moves += 1
        cur[n] = masked[n, l2, k2]
        stored_cur[n] = stored[n, l2, k2]
        row = m_inf[n] - cur[n]
        if G:
            base_g[n] = row[gcells].reshape(G, K)
            row[gcells] = np.inf
        j = int(row.argmin())
        bf_idx[n], bf_val[n] = j, row[j]
    return moves


def _step0(masked: np.ndarray, cap_all: np.ndarray,
           finite_all: np.ndarray) -> float:
    """Dual-ascent step size heuristic: mean finite cell cost over mean
    finite capacity. Guarded against the all-infinite-capacity and
    empty-finite-cells corners (N=0 tenants, all-infeasible tenants) so the
    batched fleet path can never divide by an empty mean."""
    finite_cells = masked[masked < BIG]
    if not (finite_all.any() and finite_cells.size):
        return 0.0
    return float(finite_cells.mean() / max(cap_all[finite_all].mean(), 1e-9))


def _dedupe_candidates(rows, max_candidates: int) -> List[np.ndarray]:
    """Distinct relaxed assignments emitted by the dual ascent, in emission
    order, truncated head/tail to ``max_candidates``."""
    uniq, seen = [], set()
    for row_ in rows:
        key = row_.tobytes()
        if key not in seen:
            seen.add(key)
            uniq.append(np.asarray(row_, np.int64))
    if len(uniq) > max_candidates:
        head = max_candidates // 4
        uniq = uniq[:head] + uniq[-(max_candidates - head):]
    return uniq


def _dedupe_candidates_arr(arr: np.ndarray,
                           max_candidates: int) -> List[np.ndarray]:
    """:func:`_dedupe_candidates` for a contiguous (iters, N) matrix: one
    ``np.unique`` over row bytes instead of a Python set — same unique
    rows, same first-occurrence emission order, same head/tail truncation.
    """
    arr = np.ascontiguousarray(arr)
    if arr.shape[1] == 0:
        return [np.zeros(0, np.int64)]
    keys = arr.view(np.dtype((np.void, arr.dtype.itemsize * arr.shape[1])))
    _, first = np.unique(keys.ravel(), return_index=True)
    uniq = [arr[i].astype(np.int64) for i in np.sort(first)]
    if len(uniq) > max_candidates:
        head = max_candidates // 4
        uniq = uniq[:head] + uniq[-(max_candidates - head):]
    return uniq


def _best_from_candidates(uniq: List[np.ndarray], masked: np.ndarray,
                          stored: np.ndarray, A: np.ndarray,
                          cap_all: np.ndarray,
                          finite_all: np.ndarray) -> Assignment:
    """Repair + polish every candidate cell vector, keep the best f64 score.
    The shared tail of the single-tenant and (uncoupled) fleet solvers."""
    N, _, K = masked.shape
    best: Optional[Assignment] = None
    fallback: Optional[Tuple[np.ndarray, np.ndarray]] = None
    for cand in uniq:
        tier, scheme = cand // K, cand % K
        if fallback is None:
            fallback = (tier.copy(), scheme.copy())
        use = _repair_vec(tier, scheme, masked, stored, A, cap_all,
                          finite_all)
        if use is None:
            continue
        _local_search_vec(tier, scheme, use, masked, stored, A, cap_all,
                          finite_all)
        total = float(masked[np.arange(N), tier, scheme].sum())
        if total < BIG and (best is None or total < best.cost):
            best = Assignment(tier.copy(), scheme.copy(), total, True)
    if best is None:
        tier, scheme = fallback if fallback is not None else (
            np.zeros(N, np.int64), np.zeros(N, np.int64))
        return Assignment(tier, scheme, float("inf"), False)
    return best


def _lockstep_local_search(tier_r: np.ndarray, scheme_r: np.ndarray,
                           use_r: np.ndarray, alive: np.ndarray,
                           jrow: np.ndarray, masked_b: np.ndarray,
                           stored_b: np.ndarray, A_fb: np.ndarray,
                           Af_b: np.ndarray, cap_b: np.ndarray,
                           budget: np.ndarray) -> None:
    """Vectorized best-improvement 1-swap descent over independent rows.

    Makes the moves of :func:`_local_search_vec` for every (tenant,
    candidate) row at once, rescanning every row's whole delta matrix each
    round: the same einsum ``use_c`` accumulation, the same room and
    gains, the same first-occurrence argmin over the flattened cell grid
    (padding cells are ``+inf`` and can never win), and the same per-row
    iteration budget ``8 * N + 64``. Rows
    deactivate independently, so the Python-level loop runs once per step
    of the longest trajectory instead of once per row.
    """
    M, n_max = tier_r.shape
    L, K = masked_b.shape[2], masked_b.shape[3]
    n_idx = np.arange(n_max)
    while alive.size:
        if alive.size <= _LOCKSTEP_TAIL:
            # the few long-trajectory survivors finish sequentially: rows
            # are independent and the sequential descent applies the same
            # update rule, so continuing with the remaining per-row move
            # budget lands on the same fixed point bit-for-bit — without
            # paying a full vectorized round per move for a handful of rows
            for r in alive:
                j = jrow[r]
                _local_search_vec(tier_r[r], scheme_r[r], use_r[r],
                                  masked_b[j], stored_b[j],
                                  A_fb[j] != 0.0, cap_b[j],
                                  np.isfinite(cap_b[j]),
                                  max_moves=int(budget[r]))
            return
        jr = jrow[alive]
        mrows = masked_b[jr]                                  # (A, N, L, K)
        srows = stored_b[jr]
        tr, sc = tier_r[alive], scheme_r[alive]
        a_idx = np.arange(alive.size)[:, None]
        cur = mrows[a_idx, n_idx[None, :], tr, sc]            # (A, N)
        stored_cur = srows[a_idx, n_idx[None, :], tr, sc]
        use_c = np.einsum("acl,al->ac", A_fb[jr], use_r[alive])
        At = np.take_along_axis(A_fb[jr], tr[:, None, :], axis=2)
        slack = ((cap_b[jr] - use_c)[:, None, :]
                 + At.transpose(0, 2, 1) * stored_cur[:, :, None])
        room = np.where(Af_b[jr][:, None, :, :], slack[..., None],
                        np.inf).min(2)                        # (A, N, L)
        ok = (mrows < BIG) & (srows <= room[..., None] + 1e-9)
        delta = np.where(ok, mrows - cur[..., None, None], np.inf)
        flat = delta.reshape(alive.size, -1)
        jarg = flat.argmin(1)
        dmin = flat[np.arange(alive.size), jarg]
        g = np.where(dmin < -1e-12)[0]
        if g.size == 0:
            break
        rows = alive[g]
        n, rem = np.divmod(jarg[g], L * K)
        l2, k2 = np.divmod(rem, K)
        l1 = tier_r[rows, n]
        k1 = scheme_r[rows, n]
        jg = jrow[rows]
        use_r[rows, l1] -= stored_b[jg, n, l1, k1]
        use_r[rows, l2] += stored_b[jg, n, l2, k2]
        tier_r[rows, n] = l2
        scheme_r[rows, n] = k2
        budget[rows] -= 1
        alive = rows[budget[rows] > 0]


def _batch_candidate_finish(solve_idx, cells: np.ndarray,
                            masked_b: np.ndarray, stored_b: np.ndarray,
                            maskeds, storeds, As, cap_alls, finite_alls,
                            Ns, K: int, max_candidates: int) -> dict:
    """Vectorized repair + 1-swap finish for the uncoupled fleet batch.

    Bit-identical per tenant to running :func:`_dedupe_candidates` +
    :func:`_best_from_candidates` in a loop (pinned by
    ``tests/test_fleet.py``), but batched on host: one scatter computes
    every candidate's usage, one einsum makes every round-0 feasibility
    decision, only rows that actually violate a capacity fall back to the
    sequential :func:`_repair_vec`, and all surviving rows descend in one
    lockstep :func:`_lockstep_local_search`. This removes the per-row
    Python/numpy dispatch that otherwise dominates fleet solves.
    """
    iters_n, Tp, n_max = cells.shape
    L = masked_b.shape[2]
    rows_of: List[List[int]] = [[] for _ in range(Tp)]
    uniq_all: List[np.ndarray] = []
    row_j: List[int] = []
    for j in range(Tp):
        t = solve_idx[j]
        uniq = _dedupe_candidates_arr(cells[:, j, :Ns[t]], max_candidates)
        for cand in uniq:
            rows_of[j].append(len(uniq_all))
            uniq_all.append(cand)
            row_j.append(j)
    M = len(uniq_all)
    jrow = np.asarray(row_j)

    # constraint rows, padded to a common C with inert (cap=inf) rows
    C_max = max(As[t].shape[0] for t in solve_idx)
    A_b = np.zeros((Tp, C_max, L), bool)
    cap_b2 = np.full((Tp, C_max), np.inf)
    fin_b = np.zeros((Tp, C_max), bool)
    for j, t in enumerate(solve_idx):
        C = As[t].shape[0]
        A_b[j, :C] = As[t]
        cap_b2[j, :C] = cap_alls[t]
        fin_b[j, :C] = finite_alls[t]
    A_fb = A_b.astype(np.float64)
    Af_b = A_b & fin_b[:, :, None]

    # decode candidates; keep each tenant's first decode as the fallback
    tier_r = np.zeros((M, n_max), np.int64)
    scheme_r = np.zeros((M, n_max), np.int64)
    fallbacks = {}
    for m, cand in enumerate(uniq_all):
        tier_r[m, :cand.shape[0]] = cand // K
        scheme_r[m, :cand.shape[0]] = cand % K
        j = row_j[m]
        if j not in fallbacks:
            fallbacks[j] = (tier_r[m, :cand.shape[0]].copy(),
                            scheme_r[m, :cand.shape[0]].copy())

    # per-row usage: one scatter, ascending-n within each row, so it is
    # bit-identical to _chosen_usage (padding rows add exact 0.0)
    sval = stored_b[jrow[:, None], np.arange(n_max)[None, :], tier_r,
                    scheme_r]
    use_r = np.zeros((M, L))
    np.add.at(use_r, (np.repeat(np.arange(M), n_max), tier_r.ravel()),
              sval.ravel())

    # round-0 repair decision for every row at once; only violating rows
    # pay the sequential eviction loop
    use_c0 = np.einsum("acl,al->ac", A_fb[jrow], use_r)
    viol = (fin_b[jrow] & (use_c0 > cap_b2[jrow] + 1e-9)).any(1)
    dead = np.zeros(M, bool)
    for m in np.where(viol)[0]:
        j = row_j[m]
        t = solve_idx[j]
        use = _repair_vec(tier_r[m, :Ns[t]], scheme_r[m, :Ns[t]],
                          maskeds[t], storeds[t], As[t], cap_alls[t],
                          finite_alls[t])
        if use is None:
            dead[m] = True
        else:
            use_r[m] = use

    budget = 8 * np.asarray([Ns[solve_idx[j]] for j in row_j]) + 64
    _lockstep_local_search(tier_r, scheme_r, use_r, np.where(~dead)[0],
                           jrow, masked_b, stored_b, A_fb, Af_b, cap_b2,
                           budget)

    out = {}
    for j in range(Tp):
        t = solve_idx[j]
        n_t = Ns[t]
        best: Optional[Assignment] = None
        for m in rows_of[j]:
            if dead[m]:
                continue
            tr, sc = tier_r[m, :n_t], scheme_r[m, :n_t]
            total = float(maskeds[t][np.arange(n_t), tr, sc].sum())
            if total < BIG and (best is None or total < best.cost):
                best = Assignment(tr.copy(), sc.copy(), total, True)
        if best is None:
            ftr, fsc = fallbacks.get(
                j, (np.zeros(n_t, np.int64), np.zeros(n_t, np.int64)))
            best = Assignment(ftr, fsc, float("inf"), False)
        out[t] = best
    return out


def capacitated_assign(
    cost: np.ndarray,            # (N,L,K)
    feasible: np.ndarray,        # (N,L,K)
    stored_gb: np.ndarray,       # (N,L,K) size occupied if cell chosen
    capacity_gb: np.ndarray,     # (L,)
    iters: int = 200,
    seed: int = 0,
    max_candidates: int = 16,
    tier_groups: Optional[np.ndarray] = None,       # (L,) group id per tier
    group_capacity_gb: Optional[np.ndarray] = None,  # (G,)
    sla_penalty: Optional[np.ndarray] = None,        # (N,L,K) violation units
    sla_lambda: float = 0.0,
) -> Assignment:
    """Vectorized capacitated OPTASSIGN.

    The Lagrangian inner solves run as one jitted ``lax.scan`` on device; the
    distinct relaxed assignments it emits are then repaired (argsort eviction)
    and polished (delta-matrix 1-swap descent) in vectorized NumPy, scoring in
    f64. Matches :func:`brute_force` on tiny instances and is orders of
    magnitude faster than :func:`capacitated_assign_ref` at N >= 1000.

    ``tier_groups``/``group_capacity_gb`` add group capacity constraints on
    top of the per-tier ones: ``sum(use[tier_groups == g]) <= group_cap[g]``.
    This is how per-provider capacity rows of the flattened multi-cloud
    ``(provider, tier)`` space enter the solver — each group is one
    provider's block of flat tiers.

    ``sla_penalty``/``sla_lambda`` extend the objective to ``cost +
    sla_lambda * sla_penalty`` (soft per-partition latency SLAs,
    :func:`repro.core.costs.sla_penalty_tensor`): the weighted penalty
    rides through the jitted Lagrangian scan, the repair, and the 1-swap
    polish exactly like cost. ``sla_lambda=0`` (or no penalty) leaves
    every array untouched — bit-identical to the pre-SLA solver.
    """
    if sla_lambda and sla_penalty is not None:
        cost = (np.asarray(cost, np.float64)
                + float(sla_lambda) * np.asarray(sla_penalty, np.float64))
    N, L, K = cost.shape
    masked = _masked(np.asarray(cost, np.float64), feasible)
    stored = np.asarray(stored_gb, np.float64)
    cap = np.asarray(capacity_gb, np.float64)
    finite_cap = np.isfinite(cap)
    A, cap_all = _constraint_rows(cap, tier_groups, group_capacity_gb)
    finite_all = np.isfinite(cap_all)

    if N == 0:
        z = np.zeros(0, np.int64)
        return Assignment(z, z.copy(), 0.0, True)

    # lam=0 greedy = the unconstrained optimum; if it fits the capacities it
    # is optimal outright and the dual ascent can be skipped entirely.
    cell0 = masked.reshape(N, -1).argmin(1)
    tier0, scheme0 = cell0 // K, cell0 % K
    use0 = _chosen_usage(stored, tier0, scheme0)
    if (~finite_all | (A @ use0 <= cap_all + 1e-9)).all():
        total = float(masked[np.arange(N), tier0, scheme0].sum())
        ok = bool(total < BIG)
        return Assignment(tier0, scheme0, total if ok else float("inf"), ok)

    step0 = _step0(masked, cap_all, finite_all)
    if tier_groups is None:
        g_of_t = np.zeros(L, np.int32)
        gcap = np.array([np.inf])
    else:
        g_of_t = np.asarray(tier_groups, np.int32)
        gcap = np.asarray(group_capacity_gb, np.float64)
    cells = np.asarray(_lagrangian_scan(
        jnp.asarray(masked), jnp.asarray(stored), jnp.asarray(cap),
        jnp.asarray(finite_cap), jnp.asarray(g_of_t), jnp.asarray(gcap),
        jnp.asarray(np.isfinite(gcap)), jnp.float32(step0), iters))

    uniq = _dedupe_candidates(cells, max_candidates)
    return _best_from_candidates(uniq, masked, stored, A, cap_all,
                                 finite_all)


# ---------------------------------------------------------------- fleet batch
def _fleet_scan_core(masked, stored, cap, finite_cap, group_of_tier, gcap,
                     finite_gcap, sgroup_of_tier, scap, finite_scap,
                     step0, sstep0, *, iters: int,
                     axis_name: Optional[str] = None):
    """Batched dual ascent over a padded tenant batch (T, N, L, K).

    The per-tenant body is element-for-element the computation of
    :func:`_lagrangian_scan` with a leading tenant axis, so each tenant's
    dual trajectory (and hence its emitted candidate cells) is bit-identical
    to a standalone solve — padding rows carry BIG cost and zero stored
    bytes, contributing exactly 0.0 to every usage sum and gradient.

    On top ride the *shared* fleet-wide constraint rows: ``sgroup_of_tier``
    maps each tier to a shared group whose usage is summed over the whole
    tenant axis (and, under ``shard_map``, ``psum``-reduced over
    ``axis_name``) before being dualized by one fleet-global multiplier
    vector. With no finite shared caps those multipliers stay exactly zero
    and the uncoupled trajectories are untouched.
    """
    T, N, L, K = masked.shape
    G = gcap.shape[1]
    S = scap.shape[0]
    flat_cost = masked.reshape(T, N, -1)
    flat_stored = stored.reshape(T, N, -1)
    t_idx = jnp.arange(T)[:, None]
    g_b = jnp.broadcast_to(group_of_tier[None, :], (T, L))

    def body(carry, it):
        lam, lam_sh = carry                      # (T, L+G), (S,)
        eff = (lam[:, :L] + jnp.take_along_axis(lam[:, L:], g_b, axis=1)
               + lam_sh[sgroup_of_tier][None, :])
        adj = flat_cost + (eff[:, None, :, None] * stored).reshape(T, N, -1)
        idx = jnp.argmin(adj, axis=2)            # (T, N)
        chosen = jnp.take_along_axis(flat_stored, idx[:, :, None],
                                     axis=2)[:, :, 0]
        use = jnp.zeros((T, L), masked.dtype).at[t_idx, idx // K].add(chosen)
        use_g = jnp.zeros((T, G), masked.dtype).at[t_idx, g_b].add(use)
        use_s = jnp.zeros(S, masked.dtype).at[sgroup_of_tier].add(use.sum(0))
        if axis_name is not None:
            use_s = jax.lax.psum(use_s, axis_name)
        grad = jnp.concatenate(
            [jnp.where(finite_cap, use - cap, 0.0),
             jnp.where(finite_gcap, use_g - gcap, 0.0)], axis=1)
        sgrad = jnp.where(finite_scap, use_s - scap, 0.0)
        lam = jnp.maximum(0.0, lam + step0[:, None] / (1.0 + it) * grad)
        lam_sh = jnp.maximum(0.0, lam_sh + sstep0 / (1.0 + it) * sgrad)
        return (lam, lam_sh), idx

    init = (jnp.zeros((T, L + G), masked.dtype), jnp.zeros(S, masked.dtype))
    _, cells = jax.lax.scan(body, init,
                            jnp.arange(iters, dtype=masked.dtype))
    return cells                                 # (iters, T, N)


@partial(jax.jit, static_argnames=("iters",))
def _fleet_scan_single(masked, stored, cap, finite_cap, group_of_tier, gcap,
                       finite_gcap, sgroup_of_tier, scap, finite_scap,
                       step0, sstep0, iters):
    return _fleet_scan_core(masked, stored, cap, finite_cap, group_of_tier,
                            gcap, finite_gcap, sgroup_of_tier, scap,
                            finite_scap, step0, sstep0, iters=iters)


# uncoupled fleets run the lean kernel in fixed-size tenant chunks so one
# compiled (chunk, N_max) shape is reused for any fleet size
_FLEET_CHUNK = 64

# below this many alive rows the lockstep descent hands the stragglers to
# the sequential per-row search (same trajectory, no per-round overhead)
_LOCKSTEP_TAIL = 8


@partial(jax.jit, static_argnames=("iters",))
def _fleet_scan_plain(masked, stored, cap, finite_cap, step0, iters):
    """Per-tier-caps-only batched dual ascent — :func:`_fleet_scan_core`
    with the group and shared-row machinery elided.

    With no finite group or shared caps those multipliers stay exactly 0.0
    in the general kernel (their gradients are masked to zero), so every
    surviving expression here is element-for-element the same computation
    and the emitted cells are bit-identical — at roughly half the per-step
    op count, which matters on CPU where the scan is dispatch-bound.
    """
    T, N, L, K = masked.shape
    flat_cost = masked.reshape(T, N, -1)
    flat_stored = stored.reshape(T, N, -1)
    t_idx = jnp.arange(T)[:, None]

    def body(lam, it):
        adj = flat_cost + (lam[:, None, :, None] * stored).reshape(T, N, -1)
        idx = jnp.argmin(adj, axis=2)            # (T, N)
        chosen = jnp.take_along_axis(flat_stored, idx[:, :, None],
                                     axis=2)[:, :, 0]
        use = jnp.zeros((T, L), masked.dtype).at[t_idx, idx // K].add(chosen)
        grad = jnp.where(finite_cap, use - cap, 0.0)
        lam = jnp.maximum(0.0, lam + step0[:, None] / (1.0 + it) * grad)
        return lam, idx

    _, cells = jax.lax.scan(body, jnp.zeros((T, L), masked.dtype),
                            jnp.arange(iters, dtype=masked.dtype))
    return cells                                 # (iters, T, N)


def _run_fleet_scan(mesh, masked_b, stored_b, cap_b, gcap_b, g_of_t,
                    sg_of_t, scap, sstep0, step0_b, iters: int) -> np.ndarray:
    """Dispatch the batched scan — one ``shard_map`` over the tenant axis of
    ``mesh``'s first axis when it spans >1 device, plain jit otherwise."""
    args = lambda mb, sb, cb, s0: (
        jnp.asarray(mb), jnp.asarray(sb), jnp.asarray(cb),
        jnp.asarray(np.isfinite(cb)), jnp.asarray(g_of_t),
        jnp.asarray(gcap_b), jnp.asarray(np.isfinite(gcap_b)),
        jnp.asarray(sg_of_t), jnp.asarray(scap),
        jnp.asarray(np.isfinite(scap)), jnp.asarray(s0, jnp.float32),
        jnp.float32(sstep0))
    ndev = 1 if mesh is None else int(np.prod(list(mesh.shape.values())))
    if mesh is None or ndev <= 1:
        if not (np.isfinite(gcap_b).any() or np.isfinite(scap).any()):
            # group/shared duals provably stay 0.0 — use the lean kernel.
            # Tenants are fully independent here, so large fleets run in
            # fixed-size chunks: one compiled (chunk, N_max) shape serves
            # any T instead of re-compiling per fleet size, which is what
            # dominates cold solves at T >> chunk.
            T = masked_b.shape[0]
            fin_b = np.isfinite(cap_b)
            if T <= _FLEET_CHUNK:
                return np.asarray(_fleet_scan_plain(
                    jnp.asarray(masked_b), jnp.asarray(stored_b),
                    jnp.asarray(cap_b), jnp.asarray(fin_b),
                    jnp.asarray(step0_b, jnp.float32), iters))
            pad = (-T) % _FLEET_CHUNK
            if pad:
                # dummy tenants: BIG cost, zero stored bytes, unbounded
                # caps — their duals never move; sliced off below
                masked_b = np.concatenate(
                    [masked_b, np.full((pad,) + masked_b.shape[1:], BIG)])
                stored_b = np.concatenate(
                    [stored_b, np.zeros((pad,) + stored_b.shape[1:])])
                cap_b = np.concatenate(
                    [cap_b, np.full((pad,) + cap_b.shape[1:], np.inf)])
                fin_b = np.isfinite(cap_b)
                step0_b = np.concatenate([step0_b, np.zeros(pad)])
            chunks = [np.asarray(_fleet_scan_plain(
                jnp.asarray(masked_b[i:i + _FLEET_CHUNK]),
                jnp.asarray(stored_b[i:i + _FLEET_CHUNK]),
                jnp.asarray(cap_b[i:i + _FLEET_CHUNK]),
                jnp.asarray(fin_b[i:i + _FLEET_CHUNK]),
                jnp.asarray(step0_b[i:i + _FLEET_CHUNK], jnp.float32),
                iters)) for i in range(0, T + pad, _FLEET_CHUNK)]
            cells = np.concatenate(chunks, axis=1)
            return cells[:, :T] if pad else cells
        return np.asarray(_fleet_scan_single(
            *args(masked_b, stored_b, cap_b, step0_b), iters))
    from jax.sharding import PartitionSpec as P
    from repro.distributed import ctx as dist_ctx
    T = masked_b.shape[0]
    pad = (-T) % ndev
    if pad:
        # dummy tenants: BIG cost, zero stored bytes, unbounded caps —
        # their duals never move and they are sliced off below
        masked_b = np.concatenate(
            [masked_b, np.full((pad,) + masked_b.shape[1:], BIG)])
        stored_b = np.concatenate(
            [stored_b, np.zeros((pad,) + stored_b.shape[1:])])
        cap_b = np.concatenate(
            [cap_b, np.full((pad,) + cap_b.shape[1:], np.inf)])
        gcap_b = np.concatenate(
            [gcap_b, np.full((pad,) + gcap_b.shape[1:], np.inf)])
        step0_b = np.concatenate([step0_b, np.zeros(pad)])
    axis = mesh.axis_names[0]
    sharded = dist_ctx.shard_map(
        partial(_fleet_scan_core, iters=iters, axis_name=axis), mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P(axis), P(axis),
                  P(), P(), P(), P(axis), P()),
        out_specs=P(None, axis, None), check_vma=False)
    cells = np.asarray(jax.jit(sharded)(
        *args(masked_b, stored_b, cap_b, step0_b)))
    return cells[:, :T] if pad else cells


@jax.jit
def _greedy_jax_batch(cost: jnp.ndarray, feasible: jnp.ndarray):
    masked = jnp.where(feasible, cost, BIG)
    flat = masked.reshape(masked.shape[0], masked.shape[1], -1)
    idx = jnp.argmin(flat, axis=2)
    best = jnp.take_along_axis(flat, idx[:, :, None], axis=2)[:, :, 0]
    K = masked.shape[3]
    return idx // K, idx % K, best


def greedy_assign_batch(costs: Sequence[np.ndarray],
                        feasibles: Sequence[np.ndarray]) -> List[Assignment]:
    """Unbounded-capacity assignment for T ragged tenants in one device
    dispatch. Bit-identical per tenant to :func:`greedy_assign` (same f32
    argmin, same f64 host re-total); padding rows are BIG-masked and
    sliced off before scoring."""
    T = len(costs)
    if T == 0:
        return []
    Ns = [int(c.shape[0]) for c in costs]
    L, K = costs[0].shape[1], costs[0].shape[2]
    n_max = max(Ns)
    if n_max == 0:
        z = np.zeros(0, np.int64)
        return [Assignment(z.copy(), z.copy(), 0.0, True) for _ in range(T)]
    cost_b = np.full((T, n_max, L, K), BIG)
    feas_b = np.zeros((T, n_max, L, K), bool)
    for t in range(T):
        cost_b[t, :Ns[t]] = costs[t]
        feas_b[t, :Ns[t]] = feasibles[t]
    tier_b, scheme_b, best_b = map(np.asarray, _greedy_jax_batch(
        jnp.asarray(cost_b), jnp.asarray(feas_b)))
    out = []
    for t in range(T):
        n = Ns[t]
        tier = tier_b[t, :n].astype(int)
        scheme = scheme_b[t, :n].astype(int)
        ok = bool((best_b[t, :n] < BIG).all())
        n_idx = np.arange(n)
        total = float(np.asarray(costs[t], np.float64)
                      [n_idx, tier, scheme].sum()) if ok else float("inf")
        out.append(Assignment(tier, scheme, total, ok))
    return out


def _fleet_repair_shared(tiers, schemes, uses, maskeds, storeds, As,
                         cap_alls, finite_alls, A_sh, cap_sh,
                         finite_sh) -> Optional[np.ndarray]:
    """Cross-tenant greedy eviction until every finite *shared* (fleet-wide)
    capacity row is respected; per-tenant rows stay respected throughout.
    Mirrors :func:`_repair_vec` at fleet scope: each round, the cheapest-
    delta members of the most over-capacity shared row move — across any
    tenant — to cells outside that row with room in both scopes. Returns
    the (S,) shared usage vector, or None if repair is impossible."""
    T = len(tiers)
    A_shf = A_sh & finite_sh[:, None]
    su = np.zeros(cap_sh.shape[0])
    for t in range(T):
        su += A_sh @ uses[t]
    total_n = sum(int(x.shape[0]) for x in tiers)
    for _ in range(4 * total_n + 8):
        over = np.where(finite_sh & (su > cap_sh + 1e-9))[0]
        if over.size == 0:
            return su
        s = over[np.argmax((su - cap_sh)[over])]
        in_s = A_sh[s]                              # (L,)
        slack_sh = np.where(finite_sh, cap_sh - su, np.inf)
        room_sh = np.where(A_shf, slack_sh[:, None], np.inf).min(0)   # (L,)
        moves = []                                  # (delta, t, n, l2, k2)
        for t in range(T):
            if tiers[t].shape[0] == 0:
                continue
            members = np.where(in_s[tiers[t]])[0]
            if members.size == 0:
                continue
            masked, stored = maskeds[t], storeds[t]
            K = masked.shape[2]
            Af = As[t] & finite_alls[t][:, None]
            use_c = As[t] @ uses[t]
            slack_own = np.where(finite_alls[t], cap_alls[t] - use_c, np.inf)
            room_own = np.where(Af, slack_own[:, None], np.inf).min(0)  # (L,)
            cur = masked[members, tiers[t][members], schemes[t][members]]
            cur_st = stored[members, tiers[t][members], schemes[t][members]]
            ok = (masked[members] < BIG) & (stored[members]
                                            <= room_own[None, :, None] + 1e-9)
            # leaving the row needs room in the destination's shared row;
            # staying inside it is allowed iff the move strictly shrinks the
            # row's usage (better compression) — shared rows are disjoint,
            # so an in-row move touches no other shared row
            ok &= np.where(in_s[None, :, None],
                           stored[members] < cur_st[:, None, None] - 1e-9,
                           stored[members] <= room_sh[None, :, None] + 1e-9)
            delta = np.where(ok, masked[members] - cur[:, None, None],
                             np.inf).reshape(members.size, -1)
            cell = delta.argmin(1)
            d = delta[np.arange(members.size), cell]
            for m in range(members.size):
                if np.isfinite(d[m]):
                    moves.append((float(d[m]), t, int(members[m]),
                                  int(cell[m]) // K, int(cell[m]) % K))
        if not moves:
            return None
        moves.sort()
        moved = False
        for d, t, n, l2, k2 in moves:
            if su[s] <= cap_sh[s] + 1e-9:
                break
            stored = storeds[t]
            if not in_s[tiers[t][n]]:
                continue
            l1, k1 = int(tiers[t][n]), int(schemes[t][n])
            s1, s2 = stored[n, l1, k1], stored[n, l2, k2]
            # room may have shrunk this round; re-check before applying
            Af = As[t] & finite_alls[t][:, None]
            use_c = As[t] @ uses[t]
            room_own = np.where(Af[:, l2], cap_alls[t] - use_c,
                                np.inf).min() if Af[:, l2].any() else np.inf
            if in_s[l2]:
                if s2 >= s1 - 1e-9:
                    continue                        # shrink no longer strict
                room_s2 = np.inf
            else:
                slack2 = np.where(finite_sh, cap_sh - su, np.inf)
                room_s2 = np.where(A_shf[:, l2], slack2, np.inf).min() \
                    if A_shf[:, l2].any() else np.inf
            if s2 > min(room_own, room_s2) + 1e-9:
                continue
            uses[t][l1] -= s1
            uses[t][l2] += s2
            su += A_sh[:, l2] * s2 - A_sh[:, l1] * s1
            tiers[t][n], schemes[t][n] = l2, k2
            moved = True
        if not moved:
            return None
    return None


def _fleet_polish(tiers, schemes, uses, maskeds, storeds, As, cap_alls,
                  finite_alls, A_sh, cap_sh, finite_sh,
                  su: np.ndarray) -> int:
    """Round-robin 1-swap descent under the shared rows: each tenant runs
    :func:`_local_search_vec` against its own constraints augmented with the
    shared rows at their *residual* caps (fleet cap minus the other tenants'
    usage), sweeping until a full pass changes nothing. Returns the number
    of moves made."""
    T = len(tiers)
    moves = 0
    for _ in range(8):
        changed = False
        for t in range(T):
            if tiers[t].shape[0] == 0:
                continue
            own_sh = A_sh @ uses[t]
            A_aug = np.concatenate([As[t], A_sh], 0)
            cap_aug = np.concatenate([cap_alls[t], cap_sh - (su - own_sh)])
            fin_aug = np.concatenate([finite_alls[t], finite_sh])
            t0, k0 = tiers[t].copy(), schemes[t].copy()
            moves += _local_search_vec(tiers[t], schemes[t], uses[t],
                                       maskeds[t], storeds[t], A_aug,
                                       cap_aug, fin_aug)
            if not ((tiers[t] == t0).all() and (schemes[t] == k0).all()):
                changed = True
                su += A_sh @ uses[t] - own_sh
        if not changed:
            break
    return moves


def _fleet_finish_shared(solve_idx, cells: np.ndarray, maskeds, storeds, As,
                         cap_alls, finite_alls, Ns, K: int, A_sh: np.ndarray,
                         cap_sh: np.ndarray, finite_sh: np.ndarray,
                         max_candidates: int) -> dict:
    """Repair + polish finish for a fleet coupled by shared rows.

    A candidate is one scan step's cells for every tenant at once. Three
    passes run over the joint candidates, each in its own span: every
    tenant's :func:`_repair_vec`, then :func:`_fleet_repair_shared` on the
    candidates that survived, then :func:`_fleet_polish` and the f64
    score. A candidate that a repair cannot make feasible drops out. Each
    candidate's work touches only its own arrays, so the passes give what
    a candidate-by-candidate loop gives: the first candidate with the
    least score wins. Returns ``{tenant: Assignment}`` over ``solve_idx``.
    """
    Tp = len(solve_idx)
    n_max = cells.shape[2]
    m_l = [maskeds[t] for t in solve_idx]
    s_l = [storeds[t] for t in solve_idx]
    A_l = [As[t] for t in solve_idx]
    c_l = [cap_alls[t] for t in solve_idx]
    f_l = [finite_alls[t] for t in solve_idx]
    size = dict(tenants=Tp, datasets=sum(Ns[t] for t in solve_idx))
    states = []
    for cand in _dedupe_candidates(
            (cells[r].ravel() for r in range(cells.shape[0])),
            max_candidates):
        grid = cand.reshape(Tp, n_max)
        states.append(([grid[j, :Ns[t]] // K for j, t in enumerate(solve_idx)],
                       [grid[j, :Ns[t]] % K for j, t in enumerate(solve_idx)]))
    fallback = (([x.copy() for x in states[0][0]],
                 [x.copy() for x in states[0][1]]) if states else
                ([np.zeros(Ns[t], np.int64) for t in solve_idx],
                 [np.zeros(Ns[t], np.int64) for t in solve_idx]))

    with tracing.span("assign.repair", candidates=len(states), **size):
        repaired = []
        for tiers, schemes in states:
            uses = []
            for j in range(Tp):
                use = _repair_vec(tiers[j], schemes[j], m_l[j], s_l[j],
                                  A_l[j], c_l[j], f_l[j])
                if use is None:
                    break
                uses.append(use)
            else:
                repaired.append((tiers, schemes, uses))

    with tracing.span("assign.shared_repair", candidates=len(repaired),
                      **size):
        shared = []
        for tiers, schemes, uses in repaired:
            su = _fleet_repair_shared(tiers, schemes, uses, m_l, s_l, A_l,
                                      c_l, f_l, A_sh, cap_sh, finite_sh)
            if su is not None:
                shared.append((tiers, schemes, uses, su))

    with tracing.span("assign.polish", candidates=len(shared),
                      **size) as sp:
        best_score, best, moves = float("inf"), None, 0
        for tiers, schemes, uses, su in shared:
            moves += _fleet_polish(tiers, schemes, uses, m_l, s_l, A_l, c_l,
                                   f_l, A_sh, cap_sh, finite_sh, su)
            score = sum(float(m_l[j][np.arange(Ns[t]), tiers[j],
                                     schemes[j]].sum())
                        for j, t in enumerate(solve_idx))
            if score < BIG and score < best_score:
                best_score, best = score, (tiers, schemes)
        sp.set_metadata(moves=moves)

    if best is None:
        return {t: Assignment(fallback[0][j], fallback[1][j], float("inf"),
                              False) for j, t in enumerate(solve_idx)}
    return {t: Assignment(best[0][j], best[1][j],
                          float(maskeds[t][np.arange(Ns[t]), best[0][j],
                                           best[1][j]].sum()), True)
            for j, t in enumerate(solve_idx)}


@dataclasses.dataclass
class FleetAssignment:
    """Result of one batched fleet solve.

    ``assignments[t]`` is tenant t's :class:`Assignment`; ``cost`` is the
    fleet-total objective (inf if any tenant is infeasible); ``feasible``
    requires every tenant feasible *and* the shared caps respected;
    ``shared_use_gb`` is the fleet usage per shared group (None when no
    shared rows were given).
    """

    assignments: List[Assignment]
    cost: float
    feasible: bool
    shared_use_gb: Optional[np.ndarray] = None


def _per_tenant_seq(x, T: int, name: str) -> list:
    """Broadcast one vector to all T tenants, or validate a per-tenant
    sequence (list/tuple of vectors, or a (T, ...) array)."""
    if x is None:
        return [None] * T
    if isinstance(x, np.ndarray) and x.ndim == 1:
        return [x] * T
    xs = list(x)
    if len(xs) != T:
        raise ValueError(f"{name}: expected one vector or a length-{T} "
                         f"sequence, got length {len(xs)}")
    return xs


def capacitated_assign_batch(
    costs: Sequence[np.ndarray],         # T x (N_t, L, K), ragged N_t
    feasibles: Sequence[np.ndarray],     # T x (N_t, L, K)
    stored_gbs: Sequence[np.ndarray],    # T x (N_t, L, K)
    capacity_gb,                         # (L,) for all tenants, or T x (L,)
    *,
    iters: int = 200,
    seed: int = 0,
    max_candidates: int = 16,
    tier_groups: Optional[np.ndarray] = None,        # (L,) — one tier space
    group_capacity_gb=None,                          # (G,) or T x (G,)
    shared_tier_groups: Optional[np.ndarray] = None,  # (L,) fleet-wide rows
    shared_capacity_gb: Optional[np.ndarray] = None,  # (S,)
    mesh=None,
    sla_penalties: Optional[Sequence] = None,        # T x (N_t,L,K) or None
    sla_lambda: float = 0.0,
) -> FleetAssignment:
    """Solve T tenants' capacitated OPTASSIGN problems in ONE device dispatch.

    Heterogeneous tenant problems are ragged-padded into a
    ``(T, N_max, L, K)`` batch (padding rows: BIG cost, zero stored bytes —
    they contribute zero cost and zero usage, so they never perturb duals or
    capacities) and run through one batched jitted Lagrangian scan; repair
    and 1-swap polish then run per tenant on host exactly as in
    :func:`capacitated_assign`. **With no shared constraints the per-tenant
    results are bit-identical to T independent** :func:`capacitated_assign`
    **calls** (pinned by ``tests/test_fleet.py``) — same greedy shortcut,
    same dual trajectories, same candidate set, same repair/polish.

    ``shared_tier_groups``/``shared_capacity_gb`` add *fleet-wide* capacity
    rows: ``sum over all tenants of use[shared_tier_groups == s] <=
    shared_capacity_gb[s]``. This is how one provider's global capacity caps
    the whole fleet rather than each tenant separately. Shared rows are
    dualized by fleet-global multipliers in the scan; on host a
    cross-tenant eviction repair (:func:`_fleet_repair_shared`) and a
    residual-cap round-robin polish (:func:`_fleet_polish`) enforce them
    exactly.

    ``mesh`` (a ``jax.sharding.Mesh``) optionally ``shard_map``s the tenant
    axis of the scan across the mesh's first axis; shared-row usage is
    ``psum``-reduced across devices. On a single device (the default) the
    plain jitted batch is dispatched — same results.
    """
    if (shared_tier_groups is None) != (shared_capacity_gb is None):
        raise ValueError("shared_tier_groups and shared_capacity_gb must be "
                         "passed together")
    # Soft-SLA term, exactly as in capacitated_assign: folded into the
    # per-tenant cost tensors before padding, so the weighted penalty rows
    # ride the batched/sharded fleet scan too. sla_lambda=0 touches nothing.
    if sla_lambda and sla_penalties is not None:
        costs = [c if p is None
                 else (np.asarray(c, np.float64)
                       + float(sla_lambda) * np.asarray(p, np.float64))
                 for c, p in zip(costs, sla_penalties)]
    T = len(costs)
    if T == 0:
        su = (np.zeros(np.asarray(shared_capacity_gb).shape[0])
              if shared_capacity_gb is not None else None)
        return FleetAssignment([], 0.0, True, su)
    L, K = int(costs[0].shape[1]), int(costs[0].shape[2])
    caps = [np.asarray(c, np.float64) for c in
            _per_tenant_seq(np.asarray(capacity_gb, np.float64)
                            if not isinstance(capacity_gb, (list, tuple))
                            else capacity_gb, T, "capacity_gb")]
    gcaps = _per_tenant_seq(group_capacity_gb, T, "group_capacity_gb")

    maskeds, storeds, As, cap_alls, finite_alls, Ns = [], [], [], [], [], []
    for t in range(T):
        maskeds.append(_masked(np.asarray(costs[t], np.float64),
                               feasibles[t]))
        storeds.append(np.asarray(stored_gbs[t], np.float64))
        A, cap_all = _constraint_rows(caps[t], tier_groups, gcaps[t])
        As.append(A)
        cap_alls.append(cap_all)
        finite_alls.append(np.isfinite(cap_all))
        Ns.append(int(costs[t].shape[0]))

    if shared_tier_groups is not None:
        sg = np.asarray(shared_tier_groups, int)
        scap = np.asarray(shared_capacity_gb, np.float64)
        S = scap.shape[0]
        if sg.shape != (L,) or (sg.size and (sg.min() < 0 or sg.max() >= S)):
            raise ValueError(f"shared_tier_groups ids must lie in [0, {S}) "
                             f"and have shape ({L},)")
        A_sh = np.arange(S)[:, None] == sg[None, :]
        finite_sh = np.isfinite(scap)
    else:
        sg = np.zeros(L, int)
        scap = np.array([np.inf])
        A_sh = np.ones((1, L), bool)
        finite_sh = np.zeros(1, bool)
    has_shared = bool(finite_sh.any())

    # lam=0 greedy shortcut, per tenant — identical to capacitated_assign's
    tier0s, scheme0s, use0s, own_ok = [], [], [], []
    for t in range(T):
        cell0 = maskeds[t].reshape(Ns[t], -1).argmin(1) if Ns[t] \
            else np.zeros(0, np.int64)
        tier0s.append(cell0 // K)
        scheme0s.append(cell0 % K)
        use0s.append(_chosen_usage(storeds[t], tier0s[t], scheme0s[t]))
        own_ok.append(bool((~finite_alls[t]
                            | (As[t] @ use0s[t]
                               <= cap_alls[t] + 1e-9)).all()))

    def greedy_result(t: int) -> Assignment:
        total = float(maskeds[t][np.arange(Ns[t]), tier0s[t],
                                 scheme0s[t]].sum())
        ok = bool(total < BIG)
        return Assignment(tier0s[t], scheme0s[t],
                          total if ok else float("inf"), ok)

    done: dict = {}
    if has_shared:
        su0 = A_sh @ np.sum(use0s, axis=0)
        if all(own_ok) and bool((~finite_sh | (su0 <= scap + 1e-9)).all()):
            solve_idx: List[int] = []
            done = {t: greedy_result(t) for t in range(T)}
        else:
            solve_idx = list(range(T))
    else:
        done = {t: greedy_result(t) for t in range(T) if own_ok[t]}
        solve_idx = [t for t in range(T) if not own_ok[t]]

    if solve_idx:
        n_max = max(Ns[t] for t in solve_idx)
        Tp = len(solve_idx)
        masked_b = np.full((Tp, n_max, L, K), BIG)
        stored_b = np.zeros((Tp, n_max, L, K))
        cap_b = np.zeros((Tp, L))
        step0_b = np.zeros(Tp)
        if tier_groups is None:
            g_of_t = np.zeros(L, np.int32)
            gcap_b = np.full((Tp, 1), np.inf)
        else:
            g_of_t = np.asarray(tier_groups, np.int32)
            gcap_b = np.stack([np.asarray(gcaps[t], np.float64)
                               for t in solve_idx])
        for j, t in enumerate(solve_idx):
            masked_b[j, :Ns[t]] = maskeds[t]
            stored_b[j, :Ns[t]] = storeds[t]
            cap_b[j] = caps[t]
            step0_b[j] = _step0(maskeds[t], cap_alls[t], finite_alls[t])
        if has_shared:
            fleet_cells = np.concatenate(
                [maskeds[t][maskeds[t] < BIG].ravel() for t in solve_idx])
            sstep0 = (fleet_cells.mean()
                      / max(scap[finite_sh].mean(), 1e-9)
                      if fleet_cells.size else 0.0)
        else:
            sstep0 = 0.0
        with tracing.span("assign.scan"):
            cells = np.asarray(_run_fleet_scan(mesh, masked_b, stored_b, cap_b,
                                               gcap_b, g_of_t,
                                               np.asarray(sg, np.int32), scap,
                                               sstep0, step0_b, iters))

        with tracing.span("assign.finish"):
            if not has_shared:
                done.update(_batch_candidate_finish(
                    solve_idx, cells, masked_b, stored_b, maskeds, storeds, As,
                    cap_alls, finite_alls, Ns, K, max_candidates))
            else:
                done.update(_fleet_finish_shared(
                    solve_idx, cells, maskeds, storeds, As, cap_alls,
                    finite_alls, Ns, K, A_sh, scap, finite_sh,
                    max_candidates))

    assignments = [done[t] for t in range(T)]
    feasible = all(a.feasible for a in assignments)
    shared_use = None
    if shared_tier_groups is not None:
        shared_use = np.zeros(scap.shape[0])
        for t, a in enumerate(assignments):
            if a.feasible and Ns[t]:
                shared_use += A_sh @ _chosen_usage(
                    storeds[t], a.tier.astype(int), a.scheme.astype(int))
        feasible = feasible and bool(
            (~finite_sh | (shared_use <= scap + 1e-9)).all())
    cost = (float(sum(a.cost for a in assignments))
            if feasible else float("inf"))
    return FleetAssignment(assignments, cost, feasible, shared_use)


def capacitated_assign_ref(
    cost: np.ndarray,            # (N,L,K)
    feasible: np.ndarray,        # (N,L,K)
    stored_gb: np.ndarray,       # (N,L,K) size occupied if cell chosen
    capacity_gb: np.ndarray,     # (L,)
    iters: int = 200,
    seed: int = 0,
) -> Assignment:
    """Pure-Python reference: Lagrangian + repair + local search (original)."""
    N, L, K = cost.shape
    masked = _masked(cost, feasible)
    lam = np.zeros(L)
    cap = capacity_gb.copy()
    finite_cap = np.isfinite(cap)
    best: Optional[Assignment] = None
    step0 = masked[masked < BIG].mean() / max(cap[finite_cap].mean(), 1e-9) \
        if finite_cap.any() else 0.0

    def solve(lam_vec: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        adj = masked + (lam_vec[None, :, None] * stored_gb)
        flat = adj.reshape(N, -1)
        idx = flat.argmin(1)
        return idx // K, idx % K

    def repair_and_score(tier: np.ndarray, scheme: np.ndarray) -> Assignment:
        tier, scheme = tier.copy(), scheme.copy()
        use = _chosen_usage(stored_gb, tier, scheme)
        # Greedy repair: move cheapest-delta items out of over-capacity tiers.
        for l in np.argsort(-(use - cap)):
            while finite_cap[l] and use[l] > cap[l] + 1e-9:
                members = [n for n in range(N) if tier[n] == l]
                best_mv, best_delta = None, np.inf
                for n in members:
                    cur = masked[n, l, scheme[n]]
                    for l2 in range(L):
                        if l2 == l:
                            continue
                        for k2 in range(K):
                            if masked[n, l2, k2] >= BIG:
                                continue
                            room = cap[l2] - use[l2] if finite_cap[l2] else np.inf
                            if stored_gb[n, l2, k2] > room + 1e-9:
                                continue
                            delta = masked[n, l2, k2] - cur
                            if delta < best_delta:
                                best_delta, best_mv = delta, (n, l2, k2)
                if best_mv is None:
                    return Assignment(tier, scheme, float("inf"), False)
                n, l2, k2 = best_mv
                use[l] -= stored_gb[n, l, scheme[n]]
                use[l2] += stored_gb[n, l2, k2]
                tier[n], scheme[n] = l2, k2
        # 1-move local search
        improved = True
        while improved:
            improved = False
            for n in range(N):
                cur_c = masked[n, tier[n], scheme[n]]
                for l2 in range(L):
                    for k2 in range(K):
                        if masked[n, l2, k2] >= cur_c - 1e-12:
                            continue
                        new_use_l2 = use[l2] + stored_gb[n, l2, k2] \
                            - (stored_gb[n, tier[n], scheme[n]] if l2 == tier[n] else 0)
                        if finite_cap[l2] and new_use_l2 > cap[l2] + 1e-9:
                            continue
                        use[tier[n]] -= stored_gb[n, tier[n], scheme[n]]
                        use[l2] += stored_gb[n, l2, k2]
                        tier[n], scheme[n] = l2, k2
                        improved = True
                        break
                    else:
                        continue
                    break
        total = float(sum(masked[n, tier[n], scheme[n]] for n in range(N)))
        ok = total < BIG
        return Assignment(tier, scheme, total if ok else float("inf"), ok)

    for it in range(iters):
        tier, scheme = solve(lam)
        cand = repair_and_score(tier, scheme)
        if cand.feasible and (best is None or cand.cost < best.cost):
            best = cand
        use = _chosen_usage(stored_gb, tier, scheme)
        grad = np.where(finite_cap, use - cap, 0.0)
        if np.all(grad <= 1e-9) and it > 0:
            break
        lam = np.maximum(0.0, lam + step0 / (1 + it) * grad)
    if best is None:
        tier, scheme = solve(lam)
        best = repair_and_score(tier, scheme)
    return best


# ------------------------------------------------------------ budgeted moves
@jax.jit
def _knapsack_scan(order: jnp.ndarray, cents: jnp.ndarray, gb: jnp.ndarray,
                   ok: jnp.ndarray, cap_cents: jnp.ndarray,
                   cap_gb: jnp.ndarray):
    """Greedy knapsack walk over pre-ranked items as one ``lax.scan``.

    Items arrive in ``order`` (best ratio first); each is taken iff it is
    eligible and fits both remaining budgets. Returns take flags in walk
    order (scatter back through ``order`` on the host)."""

    def body(carry, i):
        rem_c, rem_g = carry
        take = ok[i] & (cents[i] <= rem_c + 1e-9) & (gb[i] <= rem_g + 1e-9)
        rem_c = rem_c - jnp.where(take, cents[i], 0.0)
        rem_g = rem_g - jnp.where(take, gb[i], 0.0)
        return (rem_c, rem_g), take

    _, takes = jax.lax.scan(body, (cap_cents, cap_gb), order)
    return takes


def _exact_moves(savings: np.ndarray, cents: np.ndarray, gb: np.ndarray,
                 cand: np.ndarray, budget_cents: float, budget_gb: float,
                 ) -> np.ndarray:
    """Exact subset enumeration (vectorized bit-matrix), tiny instances only.

    Maximizes total (priority-weighted) savings subject to both caps;
    ties broken toward the cheaper subset, then the lexicographically
    first one, so the result is deterministic."""
    idx = np.where(cand)[0]
    n = idx.size
    M = ((np.arange(1 << n)[:, None] >> np.arange(n)) & 1).astype(bool)
    tot_c = M @ cents[idx]
    tot_g = M @ gb[idx]
    obj = M @ savings[idx]
    feas = (tot_c <= budget_cents + 1e-9) & (tot_g <= budget_gb + 1e-9)
    obj = np.where(feas, obj, -np.inf)
    # lexsort keys: last key is primary — max obj, then min cost, then the
    # smallest subset id (M rows are already in lexicographic order)
    best = int(np.lexsort((np.arange(1 << n), tot_c, -obj))[0])
    keep = np.zeros(savings.shape[0], bool)
    keep[idx[M[best]]] = True
    return keep


def budgeted_moves(
    savings_cents: np.ndarray,   # (N,) projected steady-state saving per move
    move_cents: np.ndarray,      # (N,) one-off charge per move (cents)
    budget_cents: float,         # per-cycle cents cap (np.inf = unbounded)
    *,
    candidates: Optional[np.ndarray] = None,   # (N,) bool; None = all
    move_gb: Optional[np.ndarray] = None,      # (N,) bytes leaving their cell
    budget_gb: float = np.inf,                 # per-cycle GB cap
    priority: Optional[np.ndarray] = None,     # (N,) aging boost (>= 1)
    method: str = "auto",                      # 'auto' | 'greedy' | 'exact'
    exact_max: int = 12,
    paid_cents: Optional[np.ndarray] = None,   # (N,) credit already banked
) -> np.ndarray:
    """Select which candidate migrations to execute under a per-cycle budget.

    The savings-per-migration-cent knapsack of the re-optimization daemon:
    maximize total projected steady-state savings subject to a cents cap
    (and optionally a GB cap) on the one-off migration spend. The
    production path is a jnp-batched greedy-ratio walk — rank every
    candidate by ``priority * savings / cents`` on device (argsort), then
    take items in rank order while they fit both budgets (one jitted
    ``lax.scan``). ``method='exact'`` enumerates subsets instead (tiny
    instances; the validation oracle for the greedy path). ``'auto'``
    uses the exact path when there are at most ``exact_max`` candidates.

    Zero-cost moves rank first and never consume budget; with both caps
    infinite every candidate is selected (the daemon's parity mode).
    Candidates with non-positive projected savings stay eligible — the
    assignment solver already justified the move (its objective sees
    constraint and one-off terms this per-cell projection does not), and
    selection only schedules spend — but their selection value is floored
    at a priority-scaled epsilon, so they rank below every
    positive-savings candidate on BOTH paths and only fill leftover
    budget. Returns an (N,) boolean mask — always a subset of
    ``candidates``.

    ``paid_cents`` is per-move credit already banked by earlier cycles
    (the daemon's amortized move-splitting): each candidate is weighed
    against the budgets at its *residual* charge ``max(move_cents -
    paid_cents, 0)``, so an oversized move whose installments have
    accumulated eventually fits the per-cycle cap and lands.
    """
    s = np.asarray(savings_cents, np.float64)
    c = np.asarray(move_cents, np.float64)
    if paid_cents is not None:
        c = np.maximum(c - np.asarray(paid_cents, np.float64), 0.0)
    N = s.shape[0]
    cand = (np.ones(N, bool) if candidates is None
            else np.asarray(candidates, bool).copy())
    g = (np.zeros(N) if move_gb is None
         else np.asarray(move_gb, np.float64))
    pr = np.ones(N) if priority is None else np.asarray(priority, np.float64)
    if N == 0 or not cand.any():
        return np.zeros(N, bool)
    if np.isinf(budget_cents) and np.isinf(budget_gb):
        return cand
    if method not in ("auto", "greedy", "exact"):
        raise ValueError(f"unknown method {method!r}")
    val = pr * s
    val = np.where(val > 0, val, 1e-9 * pr)   # take-if-fits, ranked last
    if method == "exact" or (method == "auto"
                             and int(cand.sum()) <= exact_max):
        return _exact_moves(val, c, g, cand, budget_cents, budget_gb)

    ratio = np.where(cand, val / np.maximum(c, 1e-12), -np.inf)
    order = jnp.argsort(-jnp.asarray(ratio))
    takes = np.asarray(_knapsack_scan(
        order, jnp.asarray(c), jnp.asarray(g), jnp.asarray(cand),
        jnp.asarray(budget_cents, jnp.float32),
        jnp.asarray(budget_gb, jnp.float32)))
    keep = np.zeros(N, bool)
    keep[np.asarray(order)] = takes
    keep &= cand
    # the scan ran in f32; re-walk the selected set in f64 and shed the
    # worst-ratio items if rounding let the total creep past a cap
    while keep.any() and (c[keep].sum() > budget_cents + 1e-9
                          or g[keep].sum() > budget_gb + 1e-9):
        sel = np.where(keep)[0]
        keep[sel[np.argmin(ratio[sel])]] = False
    return keep


# ---------------------------------------------------------------- brute force
def brute_force(cost: np.ndarray, feasible: np.ndarray,
                stored_gb: Optional[np.ndarray] = None,
                capacity_gb: Optional[np.ndarray] = None,
                tier_groups: Optional[np.ndarray] = None,
                group_capacity_gb: Optional[np.ndarray] = None) -> Assignment:
    """Exact oracle by enumeration — only for tiny test instances."""
    if (tier_groups is None) != (group_capacity_gb is None):
        raise ValueError("tier_groups and group_capacity_gb must be "
                         "passed together")
    N, L, K = cost.shape
    masked = _masked(cost, feasible)
    cells = [[(l, k) for l in range(L) for k in range(K)
              if masked[n, l, k] < BIG] for n in range(N)]
    best_cost, best_pick = float("inf"), None
    for pick in itertools.product(*cells):
        if capacity_gb is not None or group_capacity_gb is not None:
            use = np.zeros(L)
            for n, (l, k) in enumerate(pick):
                use[l] += stored_gb[n, l, k]
            if capacity_gb is not None and np.any(use > capacity_gb + 1e-9):
                continue
            if group_capacity_gb is not None:
                g = np.asarray(tier_groups, int)
                gcap = np.asarray(group_capacity_gb, np.float64)
                use_g = np.zeros(gcap.shape[0])
                np.add.at(use_g, g, use)
                if np.any(use_g > gcap + 1e-9):
                    continue
        c = sum(masked[n, l, k] for n, (l, k) in enumerate(pick))
        if c < best_cost:
            best_cost, best_pick = c, pick
    if best_pick is None:
        return Assignment(np.zeros(N, int), np.zeros(N, int), float("inf"), False)
    tier = np.array([l for l, _ in best_pick])
    scheme = np.array([k for _, k in best_pick])
    return Assignment(tier, scheme, float(best_cost), True)
