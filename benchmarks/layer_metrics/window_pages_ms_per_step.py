"""Cache manager: what the window layers add to a step on the host, in
milliseconds: the seconds of the engine's ``window_pages`` spans in the
window (turning a lane's ring of window-layer blocks when its cursor enters
a new block, inside ``decode_prepare``; mapping the ring as a prompt's end
sees it, inside ``prefill_prepare``) over the window's decode steps (its
``decode_prepare`` spans). None where the program records no such span: a
model without window layers, or a program from before them."""


def read(run: dict):
    t0, t1 = run.get("window", (0, 0))
    inside = lambda name: [  # noqa: E731
        e - s for n, s, e, _ in run.get("spans", ())
        if n == name and t0 <= s < t1
    ]
    pages, steps = inside("window_pages"), inside("decode_prepare")
    if not pages or not steps:
        return None
    return 1e3 * sum(pages) / len(steps)
