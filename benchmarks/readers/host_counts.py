# Readers of what the runner counted or timed on the host's clock. Each
# takes the run record (`host`: the runner's samples; `peak`, `config`)
# and the `args` of the metric's file, and returns a number, or None
# when there was nothing to read (the harness then leaves the metric
# out of the line).
"""Per-layer metric readers over host-side samples and counters."""
import statistics

import numpy as np

STATISTICS = {"mean": statistics.fmean, "median": statistics.median,
              "p95": lambda values: float(np.percentile(values, 95))}


def samples(run: dict, key: str, statistic: str, scale: float = 1.0):
    """`statistic` of the samples under `key`, times `scale` (1e3 for
    seconds to ms, 100 for a share to %)."""
    values = run["host"].get(key)
    return scale * STATISTICS[statistic](values) if values else None


def counter(run: dict, key: str, scale: float = 1.0):
    """A counter: a number, or a {name: count} map that is summed."""
    value = run["host"].get(key)
    if value is None:
        return None
    return scale * float(sum(value.values()) if isinstance(value, dict)
                         else value)


def train_mfu_pct(run: dict):
    """tokens/s/chip x FLOPs a token needs (6P + 6LTD, recomputation not
    counted) over the chip's published bf16 peak."""
    host = run["host"]
    if "train_tok_s" not in host or not run.get("peak"):
        return None
    return (100.0 * host["train_tok_s"] * host["flops_per_token"]
            / run["peak"]["bf16_flops_per_s"])
