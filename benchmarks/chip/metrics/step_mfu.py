"""Model operations of the traced steps' real tokens (the family's
``useful_flops``: no padding rows or widths) over the traced window times
the chips' bf16 peak."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    flops = sum(run.family.useful_flops(run.config, s) for s in run.steps)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (run.trace["window_s"] * peak)
