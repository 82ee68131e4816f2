"""``backend.compiles`` (XLA compiles inside the window) in the cells
whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("backend.compiles")
