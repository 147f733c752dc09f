"""Scheduler: mean of the engine's ``decode_prepare`` spans in the window,
in milliseconds: the host's work between the last admission and the decode
call (a fresh table and lengths for every layer of the cache)."""


def read(run: dict):
    t0, t1 = run.get("window", (0, 0))
    spans = [
        e - s for name, s, e, _ in run.get("spans", ())
        if name == "decode_prepare" and t0 <= s < t1
    ]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
