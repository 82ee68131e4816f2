"""``broker.sync_ms_per_wave`` (host time blocked on device results per
service wave) in the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("broker.sync_ms_per_wave")
