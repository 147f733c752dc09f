"""Device: share of the traced seconds of a serving window in which no
operation ran on the chip."""

from benchmarks.harness.trace import idle_share


def read(run: dict):
    return idle_share(run.get("trace"))
