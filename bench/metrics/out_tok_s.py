"""Generated tokens stamped in the window over the window's seconds."""


def read(run):
    n = sum(run.in_window(t) for r in run.window.requests.values()
            for t in r.stamps)
    return n / run.window_s
