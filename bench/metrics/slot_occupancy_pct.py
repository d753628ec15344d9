"""Mean over the window's engine steps of the live slots after the step,
as a share of the slots (``ServeEngine.sched.active()``)."""


def read(run):
    steps = run.window.steps
    return 100.0 * sum(s.live for s in steps) / (len(steps) * run.n_slots)
