"""Scheduler: 95th percentile, over every request due in the window, of
first token minus the time it was due (a closed loop's request is due when
its client's last one finished), in milliseconds. A closed loop that keeps
every lane full runs at capacity, and every seed sends one interleaving of
some tens of requests: recorded here, and no PR is judged by it (PERF.md)."""

from benchmarks.harness.common import percentile


def read(run: dict):
    if not run.get("ttft_s"):
        return None
    return 1e3 * percentile(run["ttft_s"], 95)
