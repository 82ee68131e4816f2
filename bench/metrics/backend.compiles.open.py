"""``backend.compiles`` (XLA compiles and compile-cache loads inside the
window) in the open-loop cells, whose latency is ``plan_p95_s``: a burst
that stacks a width set-up did not warm compiles inside the window."""
from bench.spec import reader

read = reader("backend.compiles")
