"""End-to-end behaviour tests for the paper's system: the full FL x NOMA
loop exhibits the paper's claimed orderings on a miniature instance, and the
distributed dry-run machinery works on a small host mesh (subprocess)."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import FLConfig, NOMAConfig, get_config
from repro.data import TaskConfig
from repro.fl import compare_policies

TINY = dataclasses.replace(get_config("smollm_135m").reduced(),
                           d_model=32, d_ff=64, vocab_size=32, n_layers=2)
TASK = TaskConfig(vocab_size=32, n_topics=4, seq_len=17, seed=1)
FL = FLConfig(n_clients=10, rounds=6, local_epochs=1, local_batch=8,
              lr=0.2, samples_per_client=(24, 48), seed=1)
NCFG = NOMAConfig(n_subchannels=2)


@pytest.fixture(scope="module")
def histories():
    return compare_policies(TINY, FL, NCFG, TASK,
                            policies=("age_noma", "channel", "oma_age"),
                            rounds=6, seed=1)


@pytest.mark.slow
class TestPaperClaims:
    def test_noma_rounds_faster_than_oma(self, histories):
        """C2 end-to-end: same age-based selection, NOMA total time < OMA."""
        t_noma = histories["age_noma"].sim_time[-1]
        t_oma = histories["oma_age"].sim_time[-1]
        assert t_noma < t_oma

    def test_age_staleness_bounded_vs_channel(self, histories):
        """C3 end-to-end: age policy keeps max-age lower than channel-greedy
        (which starves far clients under a fixed topology)."""
        assert max(histories["age_noma"].max_age) \
            <= max(histories["channel"].max_age)

    def test_age_participation_broader(self, histories):
        """Age policy touches every client within N/slots rounds."""
        part = histories["age_noma"].participation
        assert np.count_nonzero(part) >= 9   # 10 clients, 4 slots, 6 rounds
        part_ch = histories["channel"].participation
        assert np.count_nonzero(part) >= np.count_nonzero(part_ch)

    def test_loss_improves(self, histories):
        h = histories["age_noma"]
        assert h.loss[-1] < h.loss[0]


class TestDryRunSmall:
    """Exercise the real dryrun path on an 8-device host mesh in a
    subprocess (the 512-device flag must not leak into this process)."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, ShapeConfig
from repro.models import zoo
from repro.launch.dryrun import abstract_params_and_specs
from repro.launch import roofline as RL
from repro.launch.mesh import mesh_info

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
minfo = mesh_info(mesh)
cfg = dataclasses.replace(get_config("%s").reduced(), vocab_size=64)
shape = ShapeConfig("t", 64, 8, "train")
policy = zoo.policy_for(cfg)
params, spec_tree = abstract_params_and_specs(cfg)
pspecs = zoo.specs_with_dims(params, spec_tree, cfg, minfo, policy)
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
bshapes = zoo.batch_shapes(cfg, shape)
bspecs = zoo.batch_specs(cfg, shape, minfo)
bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
step = zoo.make_train_step(cfg, lr=1e-3, microbatches=2,
                           param_pspecs=pspecs, batch_dim_spec="data")
ms = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                  {"loss": 0, "grad_norm": 0})
with mesh:
    lowered = jax.jit(step, in_shardings=(pshard, bshard),
                      out_shardings=(pshard, ms)).lower(params, bshapes)
compiled = lowered.compile()
mem = compiled.memory_analysis()
cost = compiled.cost_analysis()
stats = RL.collective_stats(compiled.as_text())
assert mem.temp_size_in_bytes > 0
assert cost["flops"] > 0
print("OK", cost["flops"], stats.wire_bytes, stats.count)
"""

    @pytest.mark.slow
    @pytest.mark.parametrize("arch", ["smollm_135m", "grok_1_314b",
                                      "rwkv6_7b"])
    def test_small_mesh_lower_compile(self, arch):
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT % arch],
            capture_output=True, text=True, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
            timeout=540)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout
        # a sharded train step must communicate
        flops, wire, count = out.stdout.split("OK")[1].split()
        assert float(wire) > 0 and int(count) > 0


class TestRingAttention:
    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, dataclasses
from repro.configs import get_config
from repro.models import layers as L

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = dataclasses.replace(get_config("llama4_maverick_400b_a17b").reduced(),
                          n_heads=5, n_kv_heads=1, head_dim=16)
B, S = 4, 64
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, 5, 16), jnp.float32)
k = jax.random.normal(ks[1], (B, S, 1, 16), jnp.float32)
v = jax.random.normal(ks[2], (B, S, 1, 16), jnp.float32)
ref = L.flash_attention(q, k, v, cfg, causal=True, q_chunk=16, kv_chunk=16)
with mesh:
    ring = jax.jit(lambda a, b, c: L.ring_flash_attention(
        a, b, c, cfg, mesh))(q, k, v)
err = float(jnp.max(jnp.abs(ref - ring)))
assert err < 1e-5, err
print("OK", err)
"""

    @pytest.mark.slow
    def test_ring_matches_flash(self):
        """Context-parallel ring attention == flash attention (the §Perf
        pair-2 optimization must be numerically faithful)."""
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), timeout=540)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


class TestRooflineParser:
    def test_wire_bytes_formulas(self):
        from repro.launch.roofline import _wire_bytes
        assert _wire_bytes("all-reduce", 100, 4) == pytest.approx(150.0)
        assert _wire_bytes("all-gather", 100, 4) == pytest.approx(75.0)
        assert _wire_bytes("reduce-scatter", 100, 2) == pytest.approx(50.0)
        assert _wire_bytes("collective-permute", 100, 4) == 100.0
        assert _wire_bytes("all-reduce", 100, 1) == 0.0

    def test_group_size_parsing(self):
        from repro.launch.roofline import _group_size
        assert _group_size("replica_groups=[16,16]<=[256]") == 16
        assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4

    def test_trip_count_multipliers(self):
        from repro.launch.roofline import (_parse_computations,
                                           _region_multipliers,
                                           _while_trip_counts)
        hlo = """
%cond (p: (s32[], f32[4])) -> pred[] {
  %c = s32[] constant(48)
  ROOT %cmp = pred[] compare(%iter, %c), direction=LT
}
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ar = f32[4]{0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%init), condition=%cond, body=%body
}
"""
        comps = _parse_computations(hlo)
        trips = _while_trip_counts(comps)
        assert trips.get("body") == 48
        mult = _region_multipliers(comps, trips)
        assert mult.get("body") == 48


class TestChipPeaks:
    def test_v5e_peaks_published(self):
        from repro.launch.roofline import TARGET_KIND, chip_peaks
        peaks = chip_peaks(TARGET_KIND)
        assert TARGET_KIND == "TPU v5 lite"
        assert (peaks.flops, peaks.hbm_bw) == (197e12, 819e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
    def test_unknown_device_kind_rejected(self, kind):
        from repro.launch.roofline import chip_peaks, kernel_roof_point
        with pytest.raises(ValueError, match="no published peaks"):
            chip_peaks(kind)
        with pytest.raises(ValueError, match="no published peaks"):
            kernel_roof_point(1e9, 1e9, kind)

    def test_kernel_roof_point_on_v5e(self):
        from repro.launch.roofline import TARGET_KIND, kernel_roof_point
        rp = kernel_roof_point(1e9, 1e9, TARGET_KIND)
        assert rp.bound == "memory"
        assert rp.ridge == pytest.approx(197e12 / 819e9)
        assert rp.peak_fraction == pytest.approx(819e9 / 197e12)


class TestCompileCache:
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, with nothing set in code, and otherwise to one fixed,
    git-ignored directory in the checkout."""

    def test_unset_goes_to_fixed_checkout_path(self, monkeypatch):
        import pathlib

        import jax

        from repro.launch import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.enable()
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(path) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()

    def test_env_var_is_left_to_jax(self, monkeypatch, tmp_path):
        import jax

        from repro.launch import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
