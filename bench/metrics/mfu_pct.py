"""The whole step's share of the chip's bf16 peak: the operations that the
window's engine steps need (bench/workcount.py: every prompt and generated
token through the blocks, attention over live context, the head for each
emitted token), over the window's seconds times the peak."""


def read(run):
    flops = sum(run.work.call(s.slots).flops for s in run.window.steps)
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops_per_s"])
