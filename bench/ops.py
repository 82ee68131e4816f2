"""Operations and bytes of a grid scan, counted from the cost surface.

One request of a stacked grid scan evaluates its operator's cost at every
configuration of the grid and keeps the least.  The work per configuration
is counted from the cost surface as written in the configuration's model
(the Hive-on-YARN simulator), never from an implementation of the scan:
each elementwise operation whose operands depend on the configuration
counts once; terms of the request alone (``ss + ls``, the sort constant,
``ss / build_gbps``) are counted per request and are negligible.

The grid is enumerated from flat ids, so a scan reads no configuration
from memory: what it must move is its request's parameters in and its
winner out.  The scan is therefore bound by compute, not bandwidth.
"""
from __future__ import annotations

from typing import Dict, Sequence

# per configuration, by part of the computation
DECODE = {
    "flat id -> (container index, GB index): quotient, remainder": 2,
    "indices -> float nc, cs": 2,
}
SURFACE = {
    "SMJ": {
        "shuffle = total / (net_gbps * nc)": 2,
        "per_c = total / nc": 1,
        "spill = max(1, per_c / max(cs * 0.5, 1e-3))": 4,
        "sort = K * spill / (disk_gbps * 80 * nc)": 3,
        "merge = total / (probe_gbps * nc)": 2,
        "startup + shuffle + sort + merge": 3,
    },
    "BHJ": {
        "broadcast = ss * nc / (net_gbps * nc) + c": 4,
        "probe = ls / (probe_gbps * nc)": 2,
        "startup + broadcast + build + probe": 3,
        "OOM: where(ss > mem_frac * cs, inf, .)": 3,
    },
}
REDUCE = {
    "mask of ids past the grid: compare, select": 2,
    "running least cost: compare, select cost, select id": 3,
}
PARAM_BYTES = 2 * 4        # (ss, ls) in float32
RESULT_BYTES = 4 + 4       # least cost and its flat id


def ops_per_config(impl: str) -> int:
    return sum(DECODE.values()) + sum(SURFACE[impl].values()) + \
        sum(REDUCE.values())


def scan_work(searches: float, grid_size: int,
              impls: Sequence[str]) -> Dict[str, float]:
    """Operations and bytes of ``searches`` whole-grid scans, spread
    evenly over the join implementations ``impls`` (every candidate join
    submits one request per implementation, with the same parameters,
    so the broker searches or reuses them together)."""
    per_config = sum(ops_per_config(i) for i in impls) / len(impls)
    return {"ops": searches * grid_size * per_config,
            "bytes": searches * (PARAM_BYTES + RESULT_BYTES)}
