"""Scheduler: 95th percentile over every gap between consecutive output
tokens that both fall in the window, of every request, in milliseconds: at
capacity that is the length of the slower engine steps, the ones that admit
several requests. Recorded, and no PR is judged by it (PERF.md)."""

from benchmarks.harness.common import percentile


def read(run: dict):
    if not run.get("token_gaps_s"):
        return None
    return 1e3 * percentile(run["token_gaps_s"], 95)
