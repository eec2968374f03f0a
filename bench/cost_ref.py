"""Plain reference for the paper's cost model, shared by both
configurations: the cost tensor, an exact solve where no cap binds, the
Lagrangian bound where one does, and the steady-state bill. Straight
numpy in the precision asked for; nothing of the program is imported.

With tiers l, schemes k, partition span S (GB), reads rho, compression
ratio R and decompression seconds D, a new partition placed at (l, k)
for ``months`` costs (paper Sec. IV-A, ingestion: the move is a write)::

    cost[n,l,k] = (alpha * Cs_l * months + gamma * Cw_l) * S_n / R_nk
                + beta * rho_n * (Cc * D_nk + Cr_l * S_n / R_nk)

and its bill is storage ``Cs_l * months * S/R`` plus reads
``rho * Cr_l * S/R`` plus decompression ``rho * Cc * D``.
"""

from __future__ import annotations

import numpy as np


def cost_tensor(spans, rho, R, D, pricing: dict, weights: dict,
                months: float, dtype=np.float64) -> np.ndarray:
    """(N, L, K) objective of placing each partition at each cell."""
    c = lambda x: np.asarray(x, np.float64).astype(dtype)
    Cs, Cr, Cw = (c(pricing[k]) for k in ("storage_cents_gb_month",
                                          "read_cents_gb", "write_cents_gb"))
    Cc = c(pricing["compute_cents_sec"])
    stored = c(spans)[:, None] / c(R)                         # (N, K)
    hold = c(weights["alpha"]) * Cs * c(months) + c(weights["gamma"]) * Cw
    access = c(weights["beta"]) * c(rho)[:, None, None] * (
        Cc * c(D)[:, None, :] + Cr[None, :, None] * stored[:, None, :])
    return (hold[None, :, None] * stored[:, None, :] + access).astype(dtype)


def usage(spans, R, tier, scheme, n_tiers: int, dtype=np.float64):
    """(L,) GB stored on each tier by the plan."""
    stored = (np.asarray(spans, np.float64)
              / np.asarray(R, np.float64)[np.arange(len(spans)), scheme])
    return np.bincount(tier, weights=stored.astype(dtype),
                       minlength=n_tiers).astype(dtype)


def argmin_plan(cost: np.ndarray):
    """The exact unconstrained optimum: each partition's cheapest cell."""
    N, L, K = cost.shape
    cell = cost.reshape(N, -1).argmin(1)
    return cell // K, cell % K


def capped_solve(cost, spans, R, tier: int, cap: float, iters: int = 100,
                 dtype=np.float64):
    """One cap on one tier: ``(lower bound, tier, scheme)``.

    The Lagrangian dual ``g(lam) = sum_n min_c (cost + lam * w) - lam * cap``
    (``w``: GB the cell puts on the capped tier) bounds the optimum from
    below for every ``lam >= 0``; bisection finds the ``lam`` where the
    relaxed plan's use crosses the cap. The plan returned is the relaxed
    plan on the feasible side, the rounding of the bound. When the
    unconstrained optimum fits, it is exact and the bound equals it."""
    N, L, K = cost.shape
    stored = (np.asarray(spans, np.float64)[:, None]
              / np.asarray(R, np.float64)).astype(dtype)      # (N, K)
    w = np.zeros((N, L, K), dtype)
    w[:, tier, :] = stored
    cap = dtype(cap)

    def relaxed(lam):
        adj = (cost + dtype(lam) * w).reshape(N, -1)
        cell = adj.argmin(1)
        use = w.reshape(N, -1)[np.arange(N), cell].sum(dtype=dtype)
        g = adj[np.arange(N), cell].sum(dtype=dtype) - dtype(lam) * cap
        return cell, use, float(g)

    cell, use, g = relaxed(0.0)
    if use <= cap:
        return g, cell // K, cell % K
    lo, hi = 0.0, 1.0
    while relaxed(hi)[1] > cap:
        lo, hi = hi, hi * 4.0
    best = max(g, relaxed(hi)[2])
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        cell_m, use_m, g_m = relaxed(mid)
        best = max(best, g_m)
        if use_m > cap:
            lo = mid
        else:
            hi = mid
    cell = relaxed(hi)[0]
    return best, cell // K, cell % K


def move_gain(cost, spans, R, tier, scheme, cap_tier: int,
              cap: float) -> float:
    """The most one dataset's move to another cell lowers the plan's cost
    while the capped tier stays within its cap (1e-9 GB of rounding
    room), as a share of the plan's cost. A plan no single move improves
    reads 0."""
    N, L, K = cost.shape
    n = np.arange(N)
    stored = (np.asarray(spans, np.float64)[:, None]
              / np.asarray(R, np.float64))                    # (N, K)
    w = np.zeros((N, L, K))
    w[:, cap_tier, :] = stored
    use = w[n, tier, scheme].sum()
    fits = w - w[n, tier, scheme][:, None, None] <= cap - use + 1e-9
    cur = cost[n, tier, scheme]
    delta = np.where(fits, cost - cur[:, None, None], 0.0)
    return float(max(0.0, -delta.min()) / abs(cur.sum()))


def plan_cost(cost: np.ndarray, tier, scheme) -> float:
    n = np.arange(cost.shape[0])
    return float(cost[n, tier, scheme].sum(dtype=cost.dtype))


def bill(spans, rho, R, D, tier, scheme, pricing: dict, months: float,
         dtype=np.float64) -> dict:
    """Steady-state cents of a plan: storage, read, decompression, total."""
    c = lambda x: np.asarray(x, np.float64).astype(dtype)
    n = np.arange(len(spans))
    stored = c(spans) / c(R)[n, scheme]
    d_sec = c(D)[n, scheme]
    storage = (stored * c(pricing["storage_cents_gb_month"])[tier]).sum(
        dtype=dtype) * c(months)
    read = (c(rho) * stored * c(pricing["read_cents_gb"])[tier]).sum(
        dtype=dtype)
    decomp = ((c(rho) * d_sec).sum(dtype=dtype)
              * c(pricing["compute_cents_sec"]))
    return {"storage": float(storage), "read": float(read),
            "decomp": float(decomp),
            "total": float(dtype(storage) + dtype(read) + dtype(decomp))}
