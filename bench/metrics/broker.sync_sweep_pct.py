"""Share (%) of the grid rows swept by stacked scan groups that
synchronous flushes dispatched: 100 x ``broker.sweep_rows.sync`` /
(``broker.sweep_rows.sync`` + ``broker.sweep_rows.async``), each group
counted once whatever its width."""
from bench.counters import window_counts

ROWS = ("broker.sweep_rows.sync", "broker.sweep_rows.async")


def read(ctx):
    got = window_counts(ROWS)
    if got is None:
        return None
    total = got[ROWS[0]] + got[ROWS[1]]
    return 100.0 * got[ROWS[0]] / total if total else None
