"""Median time from submit to first token over every request submitted in
the window; a request with no token by the window's close counts at its age
then, so a stall moves this metric."""
import statistics


def read(run):
    w = run.window
    ttft = [min(r.stamps[0] if r.stamps else w.t_close, w.t_close) - r.submit
            for r in w.requests.values() if w.t_open <= r.submit < w.t_close]
    return statistics.median(ttft) if ttft else None
