"""The paper's primary contribution: age-based client selection and NOMA
resource allocation for communication-efficient federated learning.

Host-side (numpy) scheduler; the device mesh consumes only the resulting
(selection mask, aggregation weights) — see repro.fl.server.
"""
from repro.core import (  # noqa: F401
    aoi,
    engine,
    matching,
    noma,
    pairing,
    plan,
    roundtime,
    scheduler,
)
from repro.core.engine import (  # noqa: F401
    EngineParams,
    EngineSchedule,
    WirelessEngine,
    engine_schedule_to_numpy,
)
from repro.core.pairing import pair_candidates  # noqa: F401
from repro.core.scheduler import (  # noqa: F401
    RoundEnv,
    Schedule,
    exhaustive_joint_reference,
    exhaustive_pairing_reference,
    schedule_age_noma,
    schedule_channel_greedy,
    schedule_random,
    schedule_round_robin,
)
