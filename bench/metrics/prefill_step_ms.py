"""Device time per prefill step of the traced window: the device time of the
program calls made inside each engine step in which some slot took prompt tokens (the engine
ran prefill_step), over the number of such steps."""


def read(run):
    d = run.step_device_s("prefill")
    return 1e3 * sum(d) / len(d) if d else None
