"""``device.idle_pct`` (share of the traced window with no op on the
device) in the cells whose throughput is ``plans_per_s.grid10m``."""
from bench.spec import reader

read = reader("device.idle_pct")
