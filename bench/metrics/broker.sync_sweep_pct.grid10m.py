"""``broker.sync_sweep_pct`` (share of swept grid rows that synchronous
flushes dispatched) in the cells whose throughput is
``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("broker.sync_sweep_pct")
