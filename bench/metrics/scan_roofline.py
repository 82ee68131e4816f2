"""Share (%) of the device's busy time that the scan's work needs at the
chip's peaks: the larger of operations / peak FLOP/s and bytes / peak
bytes/s (``bench/ops.py``, ``bench/peaks.json``), over busy seconds.  The
scan moves no per-configuration data, so the operations bound it; the only
published compute peak is the bf16 matrix peak."""
from bench import ops


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"] or not ctx.peaks:
        return None
    searches = ctx.counts["requests"] - ctx.counts["dedup_hits"]
    if searches <= 0:
        return None
    work = ops.scan_work(searches, ctx.grid_size,
                         ctx.config["cost_model"]["impls"])
    least_s = max(work["ops"] / ctx.peaks["flops_per_s"],
                  work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / ctx.trace["busy_s"]
