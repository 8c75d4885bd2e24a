"""Share of its roofline that the FedAvg kernel (``kernels/fedagg.py``,
the ``jit_fedagg_pallas`` programs) reaches: the least time for the
logical (C, N) weighted sum of every parameter leaf of every aggregation
in the traced window, over the kernel programs' device time, in percent."""
import math

import jax

from bench import peaks
from bench.costs import fedagg
from bench.reference import llama


def read(m):
    secs, n = m.reduction.module_time(r"^jit_fedagg_pallas$")
    rounds = m.counts.get("work", 0)
    if not n or not rounds:
        return None
    d = m.config["deployment"]
    cohort = min(d["n_subchannels"] * d["users_per_subchannel"],
                 d["n_clients"])
    leaves = [math.prod(s) for s in jax.tree.leaves(
        llama.shapes(m.config), is_leaf=lambda x: isinstance(x, tuple))]
    flops = rounds * sum(fedagg.flops(cohort, k) for k in leaves)
    bytes_ = rounds * sum(fedagg.bytes_(cohort, k) for k in leaves)
    return peaks.roofline_share(flops, bytes_, secs, m.device_kind)[0]
