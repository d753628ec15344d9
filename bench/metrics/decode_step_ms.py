"""Device time per decode step of the traced window: the device time of the
program calls made inside each engine step in which no slot took prompt tokens (the engine
ran decode_step), over the number of such steps."""


def read(run):
    d = run.step_device_s("decode")
    return 1e3 * sum(d) / len(d) if d else None
