"""Device: share of the traced window in which no XLA operation runs on the
chip, averaged over the cell's chips."""
from bench import trace


def read(run):
    lo, hi = run.window_ns
    busy = [trace.busy_ns(ops, run.window_ns) for ops in run.trace.devices]
    if not busy or hi <= lo:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
