"""95th percentile of the gaps between consecutive tokens of a request that
end in the window; a request's open gap at the window's close counts."""
import numpy as np


def read(run):
    w = run.window
    gaps = []
    for r in w.requests.values():
        gaps += [b - a for a, b in zip(r.stamps, r.stamps[1:])
                 if run.in_window(b)]
        if r.stamps and r.finish is None and r.stamps[-1] < w.t_close:
            gaps.append(w.t_close - r.stamps[-1])
    return float(np.percentile(gaps, 95)) if gaps else None
