"""flush_stall_pct.ycsb: share of the window's wall time spent in steps
that a memtable flush stalled, in %: the step in which the store flushed
(with the compactions it set off), and each later step that built a
program, since scans meet the new run stack first there and every stack
shape is a new program (one per scan batch size).  From the store's own
flush counter (``StoreStats.flushes``) and JAX's compile events, read
around every step."""


def read(run):
    flushed = getattr(run.system, "step_flushes", None)
    if not flushed or run.window_s <= 0:
        return None
    n = len(run.step_s)
    hit = [bool(f) for f in flushed[:n]]
    if any(hit):
        first = hit.index(True)
        for i in range(first + 1, n):
            hit[i] = hit[i] or bool(run.step_builds[i])
    return 100.0 * float(sum(s for s, h in zip(run.step_s, hit) if h)) \
        / run.window_s
