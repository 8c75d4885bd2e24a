"""FL server: round orchestration joining the paper's scheduler (core/) to
the training substrate (models/, optim/, data/).

Per round:
  1. step the wireless scenario (repro.sim.NumpyScenario — mobility,
     correlated fading, compute/data dynamics; static_iid reproduces the
     legacy block-fading stream bit-for-bit) -> gains/n_samples/cpu; build
     RoundEnv (incl. current AoU ages);
  2. run the selection policy -> Schedule (mask, pairs, powers, rates, T)
     via the shared ``select()`` path (every policy, with or without the
     update predictor);
  3. run local SGD for selected clients, folding each delta into a
     running FedAvg as it arrives (at most one delta alive at a time);
  4. when ``predictor != "none"``: collect the deltas instead, train the
     server-side ANN on the arrivals, predict deltas for unselected
     clients, and blend them in with age-discounted weights
     (repro.fl.predictor);
  5. apply the aggregate;
  6. advance ages and the simulated wall clock by T_round.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ModelConfig, NOMAConfig
from repro.core import aoi, plan
from repro.core.engine import WirelessEngine
from repro.core.scheduler import (
    RoundEnv,
    Schedule,
    schedule_age_noma,
    schedule_channel_greedy,
    schedule_random,
    schedule_round_robin,
)  # noqa: F401  (channel_greedy also used for budget auto-calibration)
from repro.data import (
    TaskConfig,
    balanced_eval_set,
    client_batches,
    partition_clients,
)
from repro.fl.aggregate import aggregate_deltas, apply_aggregate, \
    blend_deltas
from repro.fl.client import LocalTrainer
from repro.fl.predictor import UpdatePredictor
from repro.kernels.backend import resolve_impl
from repro.models import zoo
from repro.obs import RunLedger, json_safe, trace
from repro.sim import NumpyScenario, get_scenario_config


@dataclasses.dataclass
class History:
    rounds: list = dataclasses.field(default_factory=list)
    sim_time: list = dataclasses.field(default_factory=list)
    round_time: list = dataclasses.field(default_factory=list)
    accuracy: list = dataclasses.field(default_factory=list)
    loss: list = dataclasses.field(default_factory=list)
    max_age: list = dataclasses.field(default_factory=list)
    mean_age: list = dataclasses.field(default_factory=list)
    n_selected: list = dataclasses.field(default_factory=list)
    # update-predictor telemetry (all-nan / zeros when predictor == "none")
    n_predicted: list = dataclasses.field(default_factory=list)
    pred_loss: list = dataclasses.field(default_factory=list)
    pred_error: list = dataclasses.field(default_factory=list)
    # round-time decomposition + planner diagnostics (the telemetry
    # contract, DESIGN.md section 11): the bottleneck client's
    # t_comp/t_up split (sums to round_time), budget-loop eviction
    # counts, joint-swap acceptances, and the population AoU histogram
    # ((7,) list per round on metrics.AOU_BUCKET_EDGES)
    t_comp_bottleneck: list = dataclasses.field(default_factory=list)
    t_up_bottleneck: list = dataclasses.field(default_factory=list)
    n_evicted: list = dataclasses.field(default_factory=list)
    joint_swaps: list = dataclasses.field(default_factory=list)
    aou_hist: list = dataclasses.field(default_factory=list)
    # per-cell selection + handover counts (empty lists when n_cells == 1)
    sel_per_cell: list = dataclasses.field(default_factory=list)
    handovers: list = dataclasses.field(default_factory=list)
    participation: Optional[np.ndarray] = None

    def as_dict(self):
        """JSON-safe dict via ``obs.json_safe``: ndarray leaves become
        (nested) lists, non-finite floats become None (predictor telemetry
        is NaN on rounds without predictions, and bare NaN tokens break
        strict JSON parsers)."""
        return {k: json_safe(v)
                for k, v in dataclasses.asdict(self).items()}


class FLServer:
    def __init__(self, model_cfg: ModelConfig, fl: FLConfig,
                 nomacfg: NOMAConfig, task: TaskConfig, *,
                 policy: str = "age_noma",
                 agg_impl: Optional[str] = None,
                 eval_every: int = 5, seed: Optional[int] = None,
                 predictor: Optional[str] = None,
                 engine: Optional[str] = None,
                 scenario: Optional[str] = None,
                 pairing: Optional[str] = None,
                 selection: Optional[str] = None):
        # subchannel pairing policy (core/pairing.py) + admitted-set
        # selection mode (core/plan.py): explicit overrides rewrite the
        # config so the numpy planner (which reads fl.pairing/fl.selection)
        # and the jax engine stay on the same policy
        if pairing is not None:
            fl = dataclasses.replace(fl, pairing=pairing)
        if selection is not None:
            fl = dataclasses.replace(fl, selection=selection)
        from repro.core.pairing import PAIRINGS
        if fl.pairing not in PAIRINGS:
            raise ValueError(f"unknown pairing {fl.pairing!r} "
                             f"(expected one of {PAIRINGS})")
        if fl.selection not in plan.SELECTIONS:
            raise ValueError(f"unknown selection {fl.selection!r} "
                             f"(expected one of {plan.SELECTIONS})")
        self.cfg = model_cfg
        self.fl = fl
        self.noma = nomacfg
        self.task = task
        self.policy = policy
        # FedAvg reduction impl: the fused sum in the deltas' own layouts
        # whatever kernel_backend says (no kernel reads the leaves without a
        # relayout, DESIGN.md section 3); agg_impl="pallas"/"interpret"
        # stacks them for the fedagg kernel
        self.agg_impl = resolve_impl(agg_impl or "xla")
        self.eval_every = eval_every
        self.predictor_mode = fl.predictor if predictor is None else predictor
        # batched wireless engine (core/engine.py) behind FLConfig.engine;
        # the numpy scheduler stays the fp64 reference path
        self.engine_mode = fl.engine if engine is None else engine
        if self.engine_mode not in ("numpy", "jax"):
            raise ValueError(f"unknown engine {self.engine_mode!r} "
                             "(expected 'numpy' or 'jax')")
        self.engine = (WirelessEngine(nomacfg, fl,
                                      kernel_backend=fl.kernel_backend,
                                      pairing=fl.pairing)
                       if self.engine_mode == "jax" else None)
        seed = fl.seed if seed is None else seed
        self.rng = np.random.default_rng(seed + 10_000)

        # clients
        self.clients = partition_clients(fl, task)
        self.n_samples = np.array([c.n_samples for c in self.clients],
                                  dtype=np.float64)
        # wireless environment dynamics: the fp64 scenario twin
        # (repro.sim.numpy_ref) owns topology, fading, and compute/data
        # processes; static_iid consumes exactly the legacy rng stream
        # (distances, cpu at init; one Exp(1) vector per round)
        self.scenario_name = fl.scenario if scenario is None else scenario
        self.scenario = NumpyScenario(
            get_scenario_config(self.scenario_name), nomacfg, fl)
        self.distances, self.cpu_freq = self.scenario.init(
            self.rng, fl.n_clients, n_samples=self.n_samples)
        # model + trainer
        self.params, _ = zoo.init_model(jax.random.PRNGKey(seed), model_cfg)
        self.trainer = LocalTrainer(model_cfg, fl.lr, fl.momentum)
        n_params = sum(p.size for p in jax.tree.leaves(self.params))
        self.model_bits = fl.model_bits or float(n_params) * 32.0

        # server-side update predictor (own seed: must not perturb the
        # topology/selection rng stream, so none/stale/ann stay paired)
        self.predictor = None
        if self.predictor_mode != "none":
            self.predictor = UpdatePredictor(
                self.params, fl, fl.n_clients, mode=self.predictor_mode,
                seed=seed)

        self.ages = aoi.init_ages(fl.n_clients)
        self._auto_budget = None
        self.pred_stats = {"n_predicted": 0, "pred_loss": float("nan"),
                           "pred_error": float("nan")}
        self.t_sim = 0.0
        self.round_idx = 0
        self.eval_tokens = jnp.asarray(balanced_eval_set(task))
        self._eval_fn = self._make_eval()

    # -- evaluation --------------------------------------------------------
    def _make_eval(self):
        cfg = self.cfg

        @jax.jit
        def eval_fn(params, tokens):
            batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
            logits, _ = zoo.forward(cfg, params, batch, remat=False)
            pred = jnp.argmax(logits, axis=-1)
            acc = jnp.mean(pred == batch["labels"])
            loss = zoo.token_loss(cfg, logits, batch["labels"])
            return acc, loss

        return eval_fn

    def evaluate(self):
        acc, loss = self._eval_fn(self.params, self.eval_tokens)
        return float(acc), float(loss)

    # -- scheduling --------------------------------------------------------
    def select(self, env: RoundEnv) -> Schedule:
        """Shared selection path — a thin driver over the round planner
        (core/plan.py): every policy resolves to a priority vector or an
        explicit candidate set and hands off to the scheduler's planner
        drivers (numpy) or the engine stage twins (jax), so each policy
        can run with or without the update predictor, under any pairing
        policy and either ``FLConfig.selection`` mode."""
        if self.fl.n_cells > 1:
            return self._select_multicell(env)
        p = self.policy
        if p in ("age_noma", "age_noma_budget", "oma_age"):
            oma = p == "oma_age"
            t_budget = None
            if p == "age_noma_budget":
                # the paper's JOINT constraint: age priority under a
                # round-time budget (auto-calibrated to ~2x the
                # channel-greedy round time on the first round if the
                # config leaves it unset)
                if self._auto_budget is None:
                    ref = schedule_channel_greedy(env, self.noma, self.fl)
                    self._auto_budget = (self.fl.t_budget_s
                                         or 2.0 * max(ref.t_round, 1e-6))
                t_budget = self._auto_budget
            if self.engine is not None:
                if t_budget is not None:
                    return self.engine.schedule(env, t_budget=t_budget,
                                                oma=oma, policy=p)
                return self.engine.schedule(env, oma=oma, policy=p)
            if t_budget is None:
                return schedule_age_noma(env, self.noma, self.fl, oma=oma)
            flb = dataclasses.replace(self.fl, t_budget_s=t_budget)
            return schedule_age_noma(env, self.noma, flb, oma=oma)
        # non-age policies: the engine path expresses each as a priority
        # vector (full engine coverage of POLICIES); the numpy side goes
        # through the scheduler's thin planner drivers
        n = self.fl.n_clients
        slots = min(self.noma.n_subchannels
                    * self.noma.users_per_subchannel, n)
        if p == "random":
            if self.engine is not None:
                return self.engine.schedule(
                    env, t_budget=0.0, policy=p,
                    priority=self.rng.uniform(size=n))
            return schedule_random(self.rng, env, self.noma, self.fl)
        if p == "channel":
            if self.engine is not None:
                return self.engine.schedule(env, t_budget=0.0, policy=p,
                                            priority=env.gains)
            return schedule_channel_greedy(env, self.noma, self.fl)
        if p == "round_robin":
            if self.engine is not None:
                from repro.core.engine import round_robin_priority
                return self.engine.schedule(
                    env, t_budget=0.0, policy=p,
                    priority=round_robin_priority(self.round_idx, n, slots))
            return schedule_round_robin(self.round_idx, env, self.noma,
                                        self.fl)
        raise ValueError(f"unknown policy {p!r}")

    def _select_multicell(self, env: RoundEnv) -> Schedule:
        """Multi-cell dispatch (``FLConfig.n_cells > 1``): every policy
        resolves to a priority vector and hands off to the
        cell-partitioned planner (``plan.plan_multicell`` / the engine's
        cell-blocked twin) with the scenario's current serving-BS
        association — each cell schedules its own K subchannels via the
        exact single-cell staged pipeline, global round time = max over
        cells, aggregation weights pooled across cells."""
        p = self.policy
        n = self.fl.n_clients
        cellv = np.asarray(self.scenario.cell)
        oma = p == "oma_age"
        t_budget = None
        priority = None  # None => the paper's age priority
        if p in ("age_noma", "age_noma_budget", "oma_age"):
            if p == "age_noma_budget":
                if self._auto_budget is None:
                    # budget auto-calibration mirrors the single-cell
                    # path but against the multi-cell channel-greedy
                    # round time (max over cells)
                    ref = plan.plan_multicell(
                        env, cellv, self.fl.n_cells, self.noma, self.fl,
                        priority=np.asarray(env.gains, np.float64))
                    self._auto_budget = (self.fl.t_budget_s
                                         or 2.0 * max(ref.t_round, 1e-6))
                t_budget = self._auto_budget
        elif p == "random":
            priority = self.rng.uniform(size=n)
            t_budget = 0.0
        elif p == "channel":
            priority = np.asarray(env.gains, np.float64)
            t_budget = 0.0
        elif p == "round_robin":
            # rotating-window priority (engine round_robin_priority twin);
            # per cell the window picks that cell's earliest members in
            # the rotation order
            slots = min(self.noma.n_subchannels
                        * self.noma.users_per_subchannel, n)
            start = (self.round_idx * slots) % n
            priority = -(((np.arange(n) - start) % n).astype(np.float64))
            t_budget = 0.0
        else:
            raise ValueError(f"unknown policy {p!r}")
        if self.engine is not None:
            return self.engine.schedule(
                env, t_budget=t_budget, oma=oma, policy=p,
                priority=priority, cell=cellv)
        if priority is None:
            priority = plan.age_score(env, self.fl)
        return plan.plan_multicell(env, cellv, self.fl.n_cells, self.noma,
                                   self.fl, priority=priority, oma=oma,
                                   t_budget=t_budget or None,  # 0.0 => none
                                   info={"policy": p, "engine": "numpy"})
    def run_round(self) -> Schedule:
        """One round, spanned at each phase (``obs.trace``, every span with
        ``r`` = the round index): ``server.round`` over
        ``server.scenario``, ``server.select``, ``server.train`` (counts
        ``clients`` and ``steps``, the SGD steps dispatched; per client a
        ``client.update``, with its ``steps``, ``pulls`` (the device-to-host
        transfers of its metrics: one a client) and a MoE model's routing
        counters, then a ``server.fold`` of its delta into the running
        FedAvg, with the ``client`` and the delta's ``bytes``, and
        ``stacked_bytes`` where a kernel path stacks) and
        ``server.aggregate`` (counts ``clients``;
        ``apply_aggregate``, fenced on the new parameters;
        ``server.predict`` inside it under the predictor)."""
        r = self.round_idx
        with trace.span("server.round", r=r):
            # advance the wireless environment; under dynamic scenarios the
            # env's n_samples only shape the SCHEDULER's view (age priority
            # weighting + T_cmp) — local batches and aggregation weights
            # stay tied to the fixed client datasets, so real and predicted
            # deltas share one weight convention
            with trace.span("server.scenario", r=r):
                gains, env_n_samples, env_cpu = self.scenario.step(self.rng)
                env = RoundEnv(gains=gains, n_samples=env_n_samples,
                               cpu_freq=env_cpu, ages=self.ages,
                               model_bits=self.model_bits)
            with trace.span("server.select", r=r):
                sched = self.select(env)

            sel = np.flatnonzero(sched.selected)
            # running FedAvg: each delta is folded into the aggregate as it
            # arrives, so at most one delta is alive beside it; the
            # predictor, which flattens every delta, keeps the list
            agg, total, deltas, weights = None, 0.0, [], []
            with trace.span("server.train", r=r, clients=len(sel)) as sp:
                steps = 0
                for ci in sel:
                    with trace.span("client.update", r=r,
                                    client=int(ci)) as cu:
                        batches = list(client_batches(
                            self.rng, self.clients[ci], self.fl.local_batch,
                            self.fl.local_epochs))
                        cu.note(steps=len(batches))
                        delta, _ = self.trainer.local_update(self.params,
                                                             batches)
                    steps += len(batches)
                    w = self.n_samples[ci]
                    if self.predictor is not None:
                        deltas.append(delta)
                        weights.append(w)
                        continue
                    # a stacking aggregation notes stacked_bytes over the 0
                    with trace.span("server.fold", r=r, client=int(ci),
                                    bytes=sum(x.nbytes for x in
                                              jax.tree.leaves(delta)),
                                    stacked_bytes=0):
                        agg = aggregate_deltas(
                            [delta] if agg is None else [agg, delta],
                            np.array([w] if agg is None else [total, w]),
                            impl=self.agg_impl)
                    total += w
                    del delta
                sp.note(steps=steps)
            self.pred_stats = {"n_predicted": 0, "pred_loss": float("nan"),
                               "pred_error": float("nan")}
            if len(sel):
                with trace.span("server.aggregate", r=r,
                                clients=len(sel)) as sp:
                    if self.predictor is not None:
                        agg = self._aggregate_with_predictions(
                            sel, deltas, weights)
                    self.params = apply_aggregate(self.params, agg)
                    sp.fence(self.params)

            self.ages = aoi.update_ages(self.ages, sched.selected)
            self.t_sim += sched.t_round
            self.round_idx += 1
        return sched

    def _aggregate_with_predictions(self, sel, deltas, weights):
        """Predictor path: train on arrivals, predict the unselected (span
        ``server.predict``), blend with age-discounted weights -> the
        aggregate delta."""
        pred = self.predictor
        with trace.span("server.predict", r=self.round_idx):
            data_w = self.n_samples / self.n_samples.sum()
            flat = [pred.flatten(d) for d in deltas]
            stats = pred.observe(sel, flat, self.ages, data_w)

            w_real = np.asarray(weights, np.float64)
            wn = w_real / w_real.sum()
            mean_flat = sum(wi * f for wi, f in zip(wn, flat))
            selected = np.zeros(self.fl.n_clients, bool)
            selected[sel] = True
            targets = pred.predictable(selected, self.ages)
            pred_flats = pred.predict(targets, self.ages, data_w, mean_flat)
            pred_trees = [pred.unflatten(f) for f in pred_flats]
            w_pred = (self.n_samples[targets] * self.fl.pred_blend
                      * aoi.age_discount(self.ages[targets],
                                         self.fl.pred_discount))
            self.pred_stats = {"n_predicted": len(targets), **stats}
        return blend_deltas(deltas, w_real, pred_trees, w_pred,
                            impl=self.agg_impl)

    # -- full experiment ---------------------------------------------------
    def run(self, rounds: Optional[int] = None, *, verbose: bool = False,
            ledger: Optional[RunLedger] = None) -> History:
        """Run ``rounds`` FL rounds -> ``History``. Each round's planner
        diagnostics (``plan.schedule_diag``) are folded into the history;
        the whole run is recorded to a JSONL run ledger under
        ``experiments/runs/`` (pass ``ledger`` to reuse an open one;
        ``REPRO_LEDGER=0`` disables)."""
        rounds = rounds or self.fl.rounds
        hist = History()
        part = np.zeros(self.fl.n_clients)
        own_ledger = ledger is None
        if own_ledger:
            ledger = RunLedger.open("fl_run", {
                "policy": self.policy, "rounds": rounds,
                "engine": self.engine_mode, "scenario": self.scenario_name,
                "predictor": self.predictor_mode,
                "fl": dataclasses.asdict(self.fl),
                "noma": dataclasses.asdict(self.noma),
                "model": dataclasses.asdict(self.cfg)})
        multicell = self.fl.n_cells > 1
        prev_cell = np.asarray(self.scenario.cell).copy() if multicell \
            else None
        try:
            for r in range(rounds):
                sched = self.run_round()
                part += sched.selected
                if r % self.eval_every == 0 or r == rounds - 1:
                    acc, loss = self.evaluate()
                cellv = (np.asarray(self.scenario.cell) if multicell
                         else None)
                diag = plan.schedule_diag(
                    sched, self.ages, cell=cellv,
                    n_cells=self.fl.n_cells)
                hist.rounds.append(r)
                hist.sim_time.append(self.t_sim)
                hist.round_time.append(sched.t_round)
                hist.accuracy.append(acc)
                hist.loss.append(loss)
                hist.max_age.append(aoi.max_age(self.ages))
                hist.mean_age.append(aoi.mean_age(self.ages))
                hist.n_selected.append(int(sched.selected.sum()))
                hist.n_predicted.append(self.pred_stats["n_predicted"])
                hist.pred_loss.append(self.pred_stats["pred_loss"])
                hist.pred_error.append(self.pred_stats["pred_error"])
                hist.t_comp_bottleneck.append(diag["t_comp_bottleneck"])
                hist.t_up_bottleneck.append(diag["t_up_bottleneck"])
                hist.n_evicted.append(diag["n_evicted"])
                hist.joint_swaps.append(diag["joint_swaps_accepted"])
                hist.aou_hist.append(diag["aou_hist"].tolist())
                if multicell:
                    hist.sel_per_cell.append(
                        diag["sel_per_cell"].tolist())
                    hist.handovers.append(
                        int(np.sum(cellv != prev_cell)))
                    prev_cell = cellv.copy()
                ledger.event(
                    "round", r=r, t_round=sched.t_round,
                    sim_time=self.t_sim, accuracy=acc, loss=loss,
                    n_selected=hist.n_selected[-1],
                    max_age=hist.max_age[-1],
                    t_comp_bottleneck=diag["t_comp_bottleneck"],
                    t_up_bottleneck=diag["t_up_bottleneck"],
                    n_evicted=diag["n_evicted"],
                    n_predicted=self.pred_stats["n_predicted"])
                if verbose and r % self.eval_every == 0:
                    print(f"[{self.policy}] round {r:3d} "
                          f"t={self.t_sim:9.1f}s "
                          f"acc={acc:.4f} loss={loss:.4f} "
                          f"max_age={hist.max_age[-1]}")
            hist.participation = part
            ledger.event("history", **hist.as_dict())
        finally:
            if own_ledger:
                ledger.close()
        return hist
