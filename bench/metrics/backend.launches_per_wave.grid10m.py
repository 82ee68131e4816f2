"""``backend.launches_per_wave`` (scan program launches per service wave)
in the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("backend.launches_per_wave")
