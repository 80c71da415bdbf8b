"""Small stand-ins of the committed cells, for the CPU tests: the same
configuration files and traffic kinds, cut to the family's ``TINY`` sizes
(a few layers, narrow widths), with a few slots and short requests."""
from __future__ import annotations

import os

import chipbench
from chipbench.harness import BENCH_DIR, Cell, load_json

ENGINE = dict(batch_slots=4, max_len=256, max_step_tokens=64,
              page_pool_gib=0.01)
CLOSED = {"loop": "closed", "clients": 4, "requests_per_client": 20,
          "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                     "min": 8, "max": 60},
          "output": {"dist": "uniform", "min": 8, "max": 16},
          "first_output": {"dist": "uniform", "min": 2, "max": 8}, "drain_s": 0, "trace_s": 2,
          "check": {"tokens": 160, "max_requests": 8}}
OPEN = {"loop": "open", "rate_per_s": 2.0, "lead_s": 2,
        "prompt": {"dist": "lognormal", "median": 20, "sigma": 1.0,
                   "min": 8, "max": 60},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 4, "max": 16},
        "drain_s": 10, "trace_s": 2, "check": {"tokens": 30,
                                               "max_requests": 4}}


def config(name: str) -> dict:
    """Configuration file ``configs/<name>.json`` at the small sizes."""
    cfg = load_json(BENCH_DIR, "configs", f"{name}.json")
    cfg.update(chipbench.family(cfg).TINY)
    cfg["engine"] = {**cfg["engine"], **ENGINE}
    return cfg


# the end-to-end metrics of an open loop, which no committed cell runs yet
OPEN_END_TO_END = [{"name": "ttft_p95_s", "unit": "s"},
                   {"name": "itl_p95_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}]


def cell(config_name: str, loop: str = "closed",
         limit: float = 0.03) -> Cell:
    """A one-chip cell over ``config(config_name)``, its logit gap held to
    ``limit`` (sound runs read up to 0.009 at these sizes, the fp8 control
    0.11 and more, over 160 tokens); a closed loop reports
    the metrics of the committed closed-loop cell of the same
    configuration kind (``decode-long``)."""
    if loop == "open":
        end_to_end, per_layer = OPEN_END_TO_END, []
    else:
        bench = load_json(os.path.dirname(os.path.dirname(BENCH_DIR)),
                          "BENCHMARK.json")
        like = next(w["name"] for w in bench["workloads"]
                    if w["traffic"] == "decode-long")

        def mine(metrics):
            return [m for m in metrics if like in m.get("workloads", [like])]

        end_to_end, per_layer = (mine(bench["end_to_end"]),
                                 mine(bench["per_layer"]))
    return Cell(name=f"{config_name}.tiny-{loop}", chips=1,
                config=config(config_name),
                traffic=CLOSED if loop == "closed" else OPEN,
                limits={"logit_gap": limit},
                end_to_end=end_to_end, per_layer=per_layer)
