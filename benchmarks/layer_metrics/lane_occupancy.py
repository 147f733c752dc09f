"""Scheduler: active lanes over slots, mean over the window's engine steps
(counted by the harness's loop after each ``engine.step``)."""

from benchmarks.harness.common import window_steps


def read(run: dict):
    steps = window_steps(run)
    if not steps or not run.get("slots"):
        return None
    return 100.0 * sum(s[1] for s in steps) / (len(steps) * run["slots"])
