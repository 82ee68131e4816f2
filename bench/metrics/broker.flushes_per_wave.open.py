"""``broker.flushes_per_wave`` (broker waves per service wave) in the open-
loop cells, whose latency is ``plan_p95_s``."""
from bench.spec import reader

read = reader("broker.flushes_per_wave")
