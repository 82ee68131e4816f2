"""``lockstep.self_ms_per_wave`` (DP driver self time per service wave) in
the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("lockstep.self_ms_per_wave")
