"""The program's cost table from a configuration's ``pricing`` group.

Both configurations price with the paper's Azure tiers (Tables I and
XII); the plain cost model they are checked against is ``cost_ref.py``.
"""

from __future__ import annotations

import numpy as np

from repro.core.costs import CostTable


def cost_table(pricing: dict) -> CostTable:
    L = len(pricing["tiers"])
    arr = lambda k: np.asarray(pricing[k], np.float64)
    return CostTable(
        storage_cents_gb_month=arr("storage_cents_gb_month"),
        read_cents_gb=arr("read_cents_gb"),
        write_cents_gb=arr("write_cents_gb"),
        ttfb_seconds=arr("ttfb_seconds"),
        capacity_gb=np.full(L, np.inf),
        early_delete_months=arr("early_delete_months"),
        compute_cents_sec=float(pricing["compute_cents_sec"]),
        names=tuple(pricing["tiers"]))
