"""``broker.commit_ms_per_wave`` (float64 commit time per service wave) in
the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("broker.commit_ms_per_wave")
