"""Host step loop: share of the window's seconds that ``fit`` waited for
its next batch (its own ``data_wait`` spans, host clock)."""


def read(run: dict):
    spans = [s for s in run.get("spans", ()) if s[0] == "data_wait"]
    if not spans or not run.get("window_s"):
        return None
    return 100.0 * sum(e - s for _, s, e, _ in spans) / run["window_s"]
