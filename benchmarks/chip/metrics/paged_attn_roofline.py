"""Roofline share of the paged attention kernels (decode and prefill):
Q K^T and P V operations and K/V/Q/O bytes at each live row's real context
(``work.attention_rows``), least time over the device time of the kernel
events (``kernels.json``: ``paged_attention`` and ``paged_prefill``)."""
from chipbench import work


def read(run):
    if run.trace is None or not run.steps:
        return None
    k = run.trace["kernels"]
    spent = k.get("paged_attention", 0.0) + k.get("paged_prefill", 0.0)
    if spent <= 0:
        return None
    calls = [c for s in run.steps for c in work.step_attention(run.config, s)]
    least = work.roofline_seconds(calls, run.peaks["bf16_flops_per_s"],
                                  run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
