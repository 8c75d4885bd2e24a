"""Model FLOPs of the cohort's local SGD steps in the traced window (the
logical count of ``bench/costs/smollm_sgd.py``) over the window times the
chip's peak, in percent."""
from bench import peaks
from bench.costs import smollm_sgd


def read(m):
    steps = m.counts.get("steps", 0)
    if not steps:
        return None
    task = m.traffic["task"]
    flops = steps * smollm_sgd.step_flops(m.config, m.traffic["local_batch"],
                                          task["seq_len"] - 1)
    pk = peaks.peaks(m.device_kind)
    return 100.0 * flops / (m.reduction.window_s * pk.flops_per_s)
