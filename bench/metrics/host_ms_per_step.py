"""Host time per engine step in the window, from the engine's step log
(``repro.serve.telemetry``): the mean over the window's steps of the
``serve.refill``, ``serve.assemble``, ``serve.dispatch`` and
``serve.commit`` phases, the host work of a step outside its wait on the
device and its token fetch.  The note gives each phase's mean and the
caller's mean time between steps.  Silent where the program keeps no step
log, or where the log's count of the window's steps differs from the
harness's."""
PHASES = ("serve.refill", "serve.assemble", "serve.dispatch", "serve.wait",
          "serve.fetch", "serve.commit")
HOST = ("serve.refill", "serve.assemble", "serve.dispatch", "serve.commit")


def read(run):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    w = run.window
    recs = telemetry.steps(w.t_open, w.t_close)
    if not recs or len(recs) != len(w.steps):
        return None
    # a record's stamps: its start, then the end of each phase in order
    stamps = [(r.t0, r.refill, r.assemble, r.dispatch, r.wait, r.fetch, r.t1)
              for r in recs]
    mean_ms = {name: 1e3 * sum(s[i + 1] - s[i] for s in stamps) / len(recs)
               for i, name in enumerate(PHASES)}
    between = [b.t0 - a.t1 for a, b in zip(recs, recs[1:])]
    between_ms = 1e3 * sum(between) / len(between) if between else 0.0
    note = (f"{len(recs)} steps; mean ms per step: "
            + ", ".join(f"{k} {v}" for k, v in mean_ms.items())
            + f"; caller between steps {between_ms}")
    return sum(mean_ms[name] for name in HOST), note
