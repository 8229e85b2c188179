"""Master reduction across chips (``repro.core.elastic`` all-gather under
sharded placement): per round, the time in which a collective runs on a
chip and no other operation does, averaged over the chips. No value where
the window holds no collective."""
from bench import trace


def read(run):
    per_dev = []
    for ops in run.trace.devices:
        if any(trace.is_collective(o.name) for o in ops):
            per_dev.append(trace.exposed_collective_ns(ops, run.window_ns))
    if not per_dev or run.rounds == 0:
        return None
    return sum(per_dev) / len(per_dev) / run.rounds / 1e6
