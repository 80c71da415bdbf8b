"""Model operations of the traced steps' real tokens (``work.useful_flops``:
no padding rows or widths) over the traced window times the chips' bf16
peak."""
from chipbench import work


def read(run):
    if run.trace is None or not run.steps:
        return None
    flops = sum(work.useful_flops(run.config, s, run.projections)
                for s in run.steps)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (run.trace["window_s"] * peak)
