"""``broker.search_pct`` (share of broker requests that needed a search) in
the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("broker.search_pct")
