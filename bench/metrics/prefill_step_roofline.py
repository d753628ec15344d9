"""Share of the roofline of the prefill_step calls in the traced window: the
least time the chip needs for the work of the window's prefill steps
(bench/workcount.py, from the shapes and live lengths), over the calls'
device time.  Silent where the trace does not hold one span per step."""


def read(run):
    return run.roofline("prefill")
