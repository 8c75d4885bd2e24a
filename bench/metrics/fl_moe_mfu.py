"""Model FLOPs of the cohort's local SGD steps in the traced window of a
MoE configuration (``bench/costs/moonlight_sgd.py``, with the routed
pairs the steps counted) over the window times the chip's peak, in
percent."""
from bench import peaks
from bench.costs import moonlight_sgd


def read(m):
    steps = m.counts.get("steps", 0)
    if not steps or "moe_routed" not in m.counts:
        return None
    b, s = m.traffic["local_batch"], m.traffic["task"]["seq_len"] - 1
    flops = (steps * moonlight_sgd.step_flops(m.config, b, s, 0.0)
             + moonlight_sgd.routed_pair_flops(m.config)
             * m.counts["moe_routed"])
    pk = peaks.peaks(m.device_kind)
    return 100.0 * flops / (m.reduction.window_s * pk.flops_per_s)
