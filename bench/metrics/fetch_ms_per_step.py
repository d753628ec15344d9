"""Mean time per engine step in the window of the ``serve.fetch`` phase,
from the engine's step log (``repro.serve.telemetry``): the ``np.asarray``
that copies the step's ready tokens from the device to the host, through
which the device idles.  The note gives the mean ``serve.wait``, the host's
wait on the device before it.  Silent where the program keeps no step log,
or where the log's count of the window's steps differs from the
harness's."""


def read(run):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    w = run.window
    recs = telemetry.steps(w.t_open, w.t_close)
    if not recs or len(recs) != len(w.steps):
        return None
    fetch = sum(r.fetch - r.wait for r in recs) / len(recs)
    wait = sum(r.wait - r.dispatch for r in recs) / len(recs)
    return 1e3 * fetch, (f"{len(recs)} steps; mean serve.wait "
                         f"{1e3 * wait} ms per step")
