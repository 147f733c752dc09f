"""Cache manager (KVBlockPool): used over used + free blocks, mean over the
window's engine steps (``scheduler.pool`` counts)."""

from benchmarks.harness.common import window_steps


def read(run: dict):
    steps = window_steps(run)
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / len(steps)
