"""Share of its roofline that the held experts' grouped products reach:
the least time of the logical products (``bench/costs/moe_gmm.py``: the
forward gate, up and down products and both backward products of each,
on the routed pairs the traced steps counted) over the device time of the
ops that compute them, XLA's ragged-dot kernels of the SGD step
(``<module>/ragged-dot-<...>``, their metadata ops not included), in
percent."""
import re

from bench import peaks
from bench.costs import moe_gmm

OPS = re.compile(r"^jit_step/ragged-dot-(?!metadata)")


def read(m):
    secs = sum(v for k, v in m.reduction.op_s.items() if OPS.search(k))
    steps = m.counts.get("steps", 0)
    routed = m.counts.get("moe_routed")
    if not secs or not steps or routed is None:
        return None
    c = m.config
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    layer_steps = steps * (c["num_hidden_layers"]
                           - c["first_k_dense_replace"])
    flops = moe_gmm.flops(d, f, routed)
    bytes_ = (moe_gmm.bytes_(d, f, c["n_routed_experts"], 0) * layer_steps
              + moe_gmm.bytes_(d, f, 0, routed))
    return peaks.roofline_share(flops, bytes_, secs, m.device_kind)[0]
