"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached: the chip's own compiler (Mosaic, XLA:TPU) runs here on the CPU
host and refuses what the chip would refuse — block shapes off the
(8, 128) tiling, scalar stores to VMEM, blocks over the scoped VMEM limit,
programs over HBM. Interpret-mode tests cannot see any of these.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import FLConfig, NOMAConfig
from repro.core.engine import EngineParams, _fast_from_env_core
from repro.kernels.fedagg import fedagg_pallas
from repro.kernels.pairscore import pairscore_pallas
from repro.kernels.planner import planner_tables_pallas

NOMA_KW = dict(n0b=1e-14, pmax=0.2, bw=1e6)
SMOLLM_EMBED = 49_152 * 576      # largest leaf of smollm_135m


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,c", [(1, 10), (8, 10), (64, 64), (4, 256)])
def test_planner_tables(one_chip, b, c):
    compiled = planner_tables_pallas.lower(
        _sds((b, c), one_chip), _sds((b, c), one_chip), _sds((), one_chip),
        **NOMA_KW).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("shape", [(64, 10, 10), (64, 256, 256)])
def test_pairscore(one_chip, shape):
    compiled = pairscore_pallas.lower(
        _sds(shape, one_chip), _sds(shape, one_chip), **NOMA_KW).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("c", [10, 50])
def test_fedagg_smollm_leaf(one_chip, c):
    """C=50 is the predictor-blend cohort at n_clients=50; a (C, 65536)
    fp32 block double-buffered would exceed the scoped VMEM."""
    compiled = fedagg_pallas.lower(
        _sds((c, SMOLLM_EMBED), one_chip), _sds((c,), one_chip)).compile()
    _assert_kernel(compiled)


# the distinct leaf shapes of smollm_135m (11 leaves: the (30, 576, 576)
# and (30, 576, 192) projections and the (30, 576) norms come in pairs)
SMOLLM_LEAVES = [(30, 576, 1536), (30, 1536, 576), (30, 576, 576),
                 (30, 576, 192), (30, 576), (49_152, 576), (576,)]
# scoped buffers the fusion keeps per operand, whatever the leaf's size
OPERAND_TEMP = 64 * 1024


@pytest.mark.parametrize("shape", SMOLLM_LEAVES)
@pytest.mark.parametrize("c", [10, 50])
def test_fused_aggregate_smollm_leaf(one_chip, c, shape):
    """The default aggregation (one fused fp32 sum over the C deltas in
    their own layouts) compiles with no relayout copy, no loop of copies
    and no concatenation, and with no temporary that grows with the leaf:
    under 64 KiB an operand, which is under one leaf's bytes for every
    leaf over C x 64 KiB, so no (C, N) stack exists."""
    from repro.fl.aggregate import _fused_sum
    deltas = [{"leaf": _sds(shape, one_chip)} for _ in range(c)]
    compiled = _fused_sum.lower(deltas, _sds((c,), one_chip)).compile()
    text = compiled.as_text()
    assert "copy(" not in text and "while" not in text
    assert "concatenate" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < c * OPERAND_TEMP


def test_engine_fast_path_hungarian(one_chip):
    """The engine's fused fast path with the planner kernel in it, at
    B=64 drops of N=1000 clients (segmented admission)."""
    ncfg = NOMAConfig(n_subchannels=5)
    prm = EngineParams.from_configs(ncfg, FLConfig())
    n_cand0 = min(prm.slots, 1000)
    step = jax.jit(functools.partial(
        _fast_from_env_core, prm=prm, gamma=1.0, oma=False,
        n_pairs=(n_cand0 + 1) // 2, n_cand0=n_cand0, pairing="hungarian",
        selection="greedy_set", admission="segmented", impl="pallas"))
    env = [_sds((64, 1000), one_chip) for _ in range(4)]
    compiled = step.lower(*env, _sds((64,), one_chip)).compile()
    _assert_kernel(compiled)
