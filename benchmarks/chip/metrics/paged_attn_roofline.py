"""Roofline share of the paged attention kernels (decode and prefill):
Q K^T and P V operations and K/V/Q/O bytes at each live row's real context
(``work.attention_rows``, counted by the family's ``step_calls``), least
time over the device time of the kernel events (``kernels.json``:
``paged_attention`` and ``paged_prefill``)."""
from chipbench import work

KERNELS = ("paged_attention", "paged_prefill")


def read(run):
    if run.trace is None or not run.steps:
        return None
    spent = sum(run.trace["kernels"].get(k, 0.0) for k in KERNELS)
    if spent <= 0:
        return None
    calls = [c for s in run.steps
             for k, cs in run.family.step_calls(run.config, s).items()
             if k in KERNELS for c in cs]
    least = work.roofline_seconds(calls, run.peaks["bf16_flops_per_s"],
                                  run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
