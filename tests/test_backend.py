"""Backend resolution tier: kernels/backend.py resolver order, the
FLConfig ``kernel_backend`` field, the WirelessEngine ``kernel_backend``
argument, and engine-level parity between the resolved backends
(DESIGN.md section 13).

These tests run on any host: branches that require a compiled Pallas
lowering (Mosaic/Triton) assert the CPU-only fallback when
``compiled_flavor()`` is None — which is the CI container — and the
compiled expectation otherwise.
"""
import warnings

import numpy as np
import pytest

from repro.configs import FLConfig, NOMAConfig
from repro.core.engine import WirelessEngine
from repro.kernels.backend import (IMPLS, compiled_flavor, resolve_backend,
                                   resolve_impl)

CFG = NOMAConfig(n_subchannels=3)


class TestResolver:
    def test_resolve_impl_passthrough(self):
        for impl in IMPLS:
            assert resolve_impl(impl) == impl

    def test_resolve_impl_eager_error(self):
        with pytest.raises(ValueError, match="unknown impl 'bogus'"):
            resolve_impl("bogus")

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown kernel_backend"):
            resolve_backend("mosaic")

    def test_xla_is_always_xla(self):
        spec = resolve_backend("xla")
        assert (spec.requested, spec.impl) == ("xla", "xla")
        assert not spec.uses_pallas

    def test_pallas_interpret_is_always_interpret(self):
        spec = resolve_backend("pallas_interpret")
        assert spec.impl == "interpret"
        assert spec.uses_pallas

    def test_auto_never_falls_back_to_interpret(self):
        """auto prefers a compiled kernel but NEVER the interpret oracle
        — on CPU-only hosts it must pick the XLA twin (the interpret
        path is a correctness oracle, 10-60x slower)."""
        spec = resolve_backend("auto")
        if compiled_flavor() is None:
            assert spec.impl == "xla"
        else:
            assert (spec.impl, spec.flavor) == ("pallas", compiled_flavor())

    def test_pallas_falls_back_to_interpret_with_warning(self):
        if compiled_flavor() is not None:
            assert resolve_backend("pallas").impl == "pallas"
            return
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            spec = resolve_backend("pallas")
        assert spec.impl == "interpret"
        assert any("falling back to interpret" in str(w.message)
                   for w in rec)


    def test_failed_probe_raises_on_compiled_platform(self, monkeypatch):
        """On a platform with a compiled lowering, a probe that fails to
        compile raises with the compiler's error — auto never quietly
        degrades to the XLA twin there."""
        from repro.kernels import backend

        def broken(*args, **kwargs):
            raise ValueError("Mosaic toolchain missing")

        monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(backend.pl, "pallas_call", broken)
        backend.compiled_flavor.cache_clear()
        try:
            with pytest.raises(RuntimeError,
                               match="mosaic.*probe.*toolchain missing"):
                resolve_backend("auto")
        finally:
            backend.compiled_flavor.cache_clear()


class TestConfigField:
    def test_default_is_auto(self):
        assert FLConfig().kernel_backend == "auto"

    def test_unknown_value_rejected_eagerly(self):
        with pytest.raises(ValueError, match="kernel_backend"):
            FLConfig(kernel_backend="triton")


class TestEngineConstruction:
    def test_default_follows_flconfig(self):
        eng = WirelessEngine(CFG, FLConfig())
        assert eng.kernel_backend == "auto"
        if compiled_flavor() is None:
            assert eng.impl == "xla"
            assert not eng.backend.uses_pallas

    def test_explicit_kernel_backend_wins_over_flconfig(self):
        eng = WirelessEngine(CFG, FLConfig(kernel_backend="pallas"),
                             kernel_backend="xla")
        assert eng.impl == "xla"


class TestBackendParity:
    """schedule_batch under kernel_backend='pallas_interpret' vs 'xla' on
    the same envs. The scoring math is identical fp32 in both impls, so
    the strong_weak path is bitwise-tight. The hungarian branch consumes
    the fused kernel's bf16 table tiles: pair costs within bf16
    resolution (~0.4%) can tie-break to a DIFFERENT near-equal-cost
    matching, so only the decisions' OUTCOMES (selected set, round time)
    are pinned there, at the bf16 tier of DESIGN.md section 13."""

    def _envs(self, seed, drops, n):
        from repro.core import noma
        rng = np.random.default_rng(seed)
        d = np.stack([noma.sample_distances(rng, n, CFG)
                      for _ in range(drops)])
        gains = np.stack([noma.sample_gains(rng, d[b], CFG)
                          for b in range(drops)])
        return (gains, rng.uniform(100, 1000, (drops, n)),
                rng.uniform(0.5e9, 2e9, (drops, n)),
                rng.integers(1, 30, (drops, n)).astype(float), 4e6)

    def _run(self, pairing, args):
        out_x = WirelessEngine(CFG, FLConfig(), kernel_backend="xla",
                               pairing=pairing).schedule_batch(*args)
        out_p = WirelessEngine(CFG, FLConfig(),
                               kernel_backend="pallas_interpret",
                               pairing=pairing).schedule_batch(*args)
        return out_x, out_p

    def test_strong_weak_is_tight(self):
        out_x, out_p = self._run("strong_weak", self._envs(42, 4, 12))
        np.testing.assert_array_equal(np.asarray(out_p.selected),
                                      np.asarray(out_x.selected))
        np.testing.assert_allclose(np.asarray(out_p.t_round),
                                   np.asarray(out_x.t_round), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out_p.rates),
                                   np.asarray(out_x.rates), rtol=1e-5)

    @pytest.mark.parametrize("seed", [42, 43])
    def test_hungarian_outcomes_within_bf16_tier(self, seed):
        out_x, out_p = self._run("hungarian", self._envs(seed, 4, 12))
        np.testing.assert_array_equal(np.asarray(out_p.selected),
                                      np.asarray(out_x.selected))
        np.testing.assert_allclose(np.asarray(out_p.t_round),
                                   np.asarray(out_x.t_round), rtol=1e-2)
