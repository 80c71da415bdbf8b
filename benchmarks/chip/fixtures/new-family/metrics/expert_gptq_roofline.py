"""Roofline share of a test kernel class, ``expert_gptq``: the family's
expert calls' least time over the class's device time."""
from chipbench import work


def read(run):
    if run.trace is None or not run.steps:
        return None
    spent = run.trace["kernels"].get("expert_gptq", 0.0)
    if spent <= 0:
        return None
    calls = [c for s in run.steps
             for c in run.family.step_calls(run.config, s)["expert_gptq"]]
    return 100.0 * work.roofline_seconds(
        calls, run.peaks["bf16_flops_per_s"],
        run.peaks["hbm_bytes_per_s"]) / spent
