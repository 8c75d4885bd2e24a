from repro.fl.aggregate import aggregate_deltas, apply_aggregate, \
    blend_deltas
from repro.fl.client import LocalTrainer
from repro.fl.predictor import UpdatePredictor
from repro.fl.rounds import compare_policies, compare_predictors, \
    run_experiment, time_to_accuracy
from repro.fl.server import FLServer, History

__all__ = [
    "FLServer", "History", "LocalTrainer", "UpdatePredictor",
    "aggregate_deltas", "apply_aggregate", "blend_deltas",
    "compare_policies", "compare_predictors", "run_experiment",
    "time_to_accuracy",
]
