"""Serving executables: seconds in ``ServingEngine.warmup`` (its ``warmup``
span: every executable compiled, or loaded from the persistent cache)."""


def read(run: dict):
    for name, s, e, _ in run.get("spans", ()):
        if name == "warmup":
            return e - s
    return None
