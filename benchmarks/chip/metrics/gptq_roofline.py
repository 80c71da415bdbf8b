"""Roofline share of the GPTQ kernels: over every GPTQ call of the traced
steps (the family's ``step_calls``, class ``gptq``), the least time the
chip could take (per call the larger of operations over the bf16 peak and
bytes over HBM bandwidth, from the call's shapes, ``work.gptq_call``) over
the device time of the kernel events (``kernels.json``: ``gptq``)."""
from chipbench import work


def read(run):
    if run.trace is None or not run.steps:
        return None
    spent = run.trace["kernels"].get("gptq", 0.0)
    if spent <= 0:
        return None
    calls = [c for s in run.steps
             for c in run.family.step_calls(run.config, s).get("gptq", [])]
    least = work.roofline_seconds(calls, run.peaks["bf16_flops_per_s"],
                                  run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
