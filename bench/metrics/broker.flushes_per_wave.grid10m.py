"""``broker.flushes_per_wave`` (broker waves per service wave) in the cells
whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("broker.flushes_per_wave")
