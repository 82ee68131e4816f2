"""``device.idle_pct`` (share of the traced window with no op on the
device) in the open-loop cells, whose latency is ``plan_p95_s``."""
from bench.spec import reader

read = reader("device.idle_pct")
