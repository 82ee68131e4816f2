"""``backend.launch_ms_per_wave`` (host time enqueueing scan programs
per service wave) in the cells whose throughput is
``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("backend.launch_ms_per_wave")
