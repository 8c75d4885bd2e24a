"""Driver of ``fl.rounds.run_montecarlo``: a closed loop of sweeps, each a
fresh seed, R rounds over S independent channel drops of one deployment.

One unit of work is one call; its drops are S x R. The call returns host
arrays, which fences it.
"""
from __future__ import annotations

import numpy as np

from bench import compare
from bench.reference import wireless


class Driver:
    unit = "call"

    def __init__(self, ctx):
        self.ctx = ctx
        self.dep = ctx.config["deployment"]
        self.tr = ctx.traffic
        ss = np.random.SeedSequence(ctx.seed)
        rng = np.random.default_rng(ss)
        # every call of the run gets its own seed; the warm-up call one more
        self.call_seeds = rng.integers(0, 2 ** 31 - 1, size=100_000)
        self.warm_seed = int(rng.integers(0, 2 ** 31 - 1))
        self.sampled = set(int(i) for i in rng.choice(
            self.tr["sample_from_first"], size=self.tr["sample_calls"],
            replace=False))
        self.kept = {}
        self.last = None
        self.calls = 0

    def _kwargs(self):
        from repro.configs import FLConfig, NOMAConfig
        from repro.sim.scenario import ScenarioConfig
        d, tr = self.dep, self.tr
        ncfg = NOMAConfig(
            n_subchannels=d["n_subchannels"],
            users_per_subchannel=d["users_per_subchannel"],
            bandwidth_hz=d["bandwidth_hz"], noise_density=d["noise_density"],
            max_power_w=d["max_power_w"], path_loss_exp=d["path_loss_exp"],
            ref_path_loss=d["ref_path_loss"],
            cell_radius_m=d["cell_radius_m"], min_radius_m=d["min_radius_m"])
        fl = FLConfig(cpu_cycles_per_sample=d["cpu_cycles_per_sample"],
                      cpu_freq_range_ghz=tuple(d["cpu_freq_range_ghz"]),
                      samples_per_client=tuple(d["samples_per_client"]),
                      local_epochs=d["local_epochs"],
                      age_exponent=d["age_exponent"],
                      kernel_backend=tr["kernel_backend"])
        sc = dict(d["scenario"])
        sc["speed_mps"] = tuple(sc["speed_mps"])
        return dict(nomacfg=ncfg, flcfg=fl, n_clients=d["n_clients"],
                    n_seeds=tr["n_seeds"], rounds=tr["rounds"],
                    policies=(tr["policy"],), model_bits=tr["model_bits"],
                    scenario=ScenarioConfig(**sc), pairing=tr["pairing"],
                    selection=tr["selection"], admission=tr["admission"],
                    kernel_backend=tr["kernel_backend"])

    def setup(self):
        from repro.fl.rounds import run_montecarlo
        self.run_montecarlo = run_montecarlo
        self.kw = self._kwargs()
        self._call(self.warm_seed)

    def _call(self, seed):
        res = self.run_montecarlo(seed=int(seed), **self.kw)
        return res[self.tr["policy"]]

    def run_unit(self) -> dict:
        i = self.calls
        out = self._call(self.call_seeds[i])
        keep = {k: out[k] for k in ("t_round", "t_comp_bottleneck",
                                    "t_up_bottleneck", "final_ages",
                                    "participation")}
        if i in self.sampled:
            self.kept[i] = keep
        self.last = (i, keep)
        self.calls += 1
        return {"work": self.tr["n_seeds"] * self.tr["rounds"]}

    def close(self):
        """After the window: free the program's state."""
        self.run_montecarlo = None

    def compared(self) -> dict:
        """The sampled calls of the window plus its last call."""
        kept = dict(self.kept)
        if self.last is not None:
            kept[self.last[0]] = self.last[1]
        return kept

    def numbers(self, dtype=None) -> dict:
        """Worst readings over the compared calls against the reference
        computed in float32; ``dtype`` computes the answers themselves
        with the reference in that precision (the control) instead of
        taking the program's."""
        import jax.numpy as jnp
        dep = wireless.params(self.ctx.config)
        ref_of = lambda i, dt: wireless.sweep(
            dep, int(self.call_seeds[i]), self.tr["n_seeds"],
            self.dep["n_clients"], self.tr["rounds"], self.tr["model_bits"],
            dtype=dt)
        diverged = n_drops = 0
        t_rel = split_rel = 0.0
        for i, got in sorted(self.compared().items()):
            if dtype is not None:
                got = ref_of(i, dtype)
            ref = ref_of(i, jnp.float32)
            same = (np.all(got["final_ages"] == ref["final_ages"], axis=1)
                    & np.all(got["participation"] == ref["participation"],
                             axis=1))
            diverged += int(np.sum(~same))
            n_drops += same.size
            t_rel = max(t_rel, compare.max_rel(got["t_round"],
                                               ref["t_round"]))
            split_rel = max(split_rel, compare.max_rel(
                got["t_comp_bottleneck"], ref["t_comp_bottleneck"]),
                compare.max_rel(got["t_up_bottleneck"],
                                ref["t_up_bottleneck"]))
        return {"drops_diverged_share": diverged / max(n_drops, 1),
                "t_round_rel": t_rel, "t_bottleneck_rel": split_rel,
                "calls_compared": len(self.compared())}
