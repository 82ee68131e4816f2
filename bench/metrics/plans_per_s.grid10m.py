"""``plans_per_s`` (joint plans resolved inside the window, over the
window's seconds) in the large-grid cells, whose bound is their own."""
from bench.spec import reader

read = reader("plans_per_s")
