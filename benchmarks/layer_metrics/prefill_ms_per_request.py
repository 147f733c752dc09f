"""Scheduler: mean of the engine's host ``prefill`` spans in the window, in
milliseconds (dispatch, device and the token read-back of one admission)."""


def read(run: dict):
    t0, t1 = run.get("window", (0, 0))
    spans = [
        e - s for name, s, e, _ in run.get("spans", ())
        if name == "prefill" and t0 <= s < t1
    ]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
