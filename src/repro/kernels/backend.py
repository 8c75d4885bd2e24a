"""Kernel backend resolution: which lowering path the engine's Pallas
kernels take on this host.

Two layers of naming (DESIGN.md section 13):

  * ``kernel_backend`` — the user-facing request on ``FLConfig`` /
    ``WirelessEngine``: one of ``KERNEL_BACKENDS``
    (``auto | xla | pallas | pallas_interpret``).
  * ``impl`` — the dispatch string every op in ``kernels/ops.py`` takes:
    one of ``IMPLS`` (``xla | pallas | interpret``).

``resolve_backend`` maps the former to the latter with runtime capability
detection, eagerly (at engine construction, not deep inside a jit trace):

  auto             compiled Pallas when the host can lower it (Mosaic on
                   TPU, Triton on GPU), else the XLA twin. Never resolves
                   to interpret: interpret mode is a correctness oracle,
                   10-60x slower than XLA (BENCH_kernels), not a perf path.
  xla              always the pure-jnp twin.
  pallas           compiled Pallas; falls back to interpret (with a
                   warning) when no compiled lowering exists — the CPU/CI
                   fallback, so parity tiers exercise the kernel body.
  pallas_interpret interpret mode unconditionally (tests, debugging).

Capability detection compiles a trivial kernel once per process
(``functools.lru_cache``) rather than trusting the platform string. On a
platform that has a compiled lowering (TPU, GPU) a failed probe raises
with the compiler's error: a broken Mosaic or Triton toolchain stops the
run at construction instead of quietly running the XLA twin under a
Pallas request.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.configs.base import KERNEL_BACKENDS

# ops.py dispatch axis (single source; every op validates against this)
IMPLS = ("xla", "pallas", "interpret")

# platform -> compiled Pallas lowering flavor
_FLAVORS = {"tpu": "mosaic", "gpu": "triton", "cuda": "triton",
            "rocm": "triton"}


def resolve_impl(impl: str) -> str:
    """Validate an ops-level ``impl`` string. Eager ValueError on unknown
    values — no silent fallthrough to the Pallas branch."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} "
                         f"(expected one of {IMPLS})")
    return impl


def _probe_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1.0


@functools.lru_cache(maxsize=None)
def compiled_flavor():
    """``"mosaic" | "triton" | None``: the compiled Pallas lowering this
    process uses, probed by compiling a trivial kernel. None only where
    the platform has no compiled lowering (the CPU); on a platform that
    has one, a probe that fails to compile or run raises."""
    platform = jax.default_backend()
    flavor = _FLAVORS.get(platform)
    if flavor is None:
        return None
    try:
        out = pl.pallas_call(
            _probe_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        )(jnp.zeros((8, 128), jnp.float32))
        jax.block_until_ready(out)
    except Exception as e:  # noqa: BLE001 — re-raised with the platform
        raise RuntimeError(
            f"the {flavor} Pallas lowering failed its probe compile on "
            f"platform {platform!r}: {e}") from e
    return flavor


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Resolved kernel backend: what was asked for and what runs."""

    requested: str   # one of KERNEL_BACKENDS
    impl: str        # one of IMPLS — the ops.py dispatch string
    flavor: str | None   # "mosaic" | "triton" | None (xla / interpret)

    @property
    def uses_pallas(self) -> bool:
        return self.impl != "xla"


def resolve_backend(kernel_backend: str = "auto") -> BackendSpec:
    """Map a ``KERNEL_BACKENDS`` request to the impl that runs here."""
    if kernel_backend not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel_backend {kernel_backend!r} "
                         f"(expected one of {KERNEL_BACKENDS})")
    flavor = compiled_flavor()
    if kernel_backend == "xla":
        return BackendSpec(kernel_backend, "xla", None)
    if kernel_backend == "pallas_interpret":
        return BackendSpec(kernel_backend, "interpret", None)
    if kernel_backend == "auto":
        if flavor is not None:
            return BackendSpec(kernel_backend, "pallas", flavor)
        return BackendSpec(kernel_backend, "xla", None)
    # "pallas": compiled when possible, interpret as the CPU/CI fallback
    if flavor is not None:
        return BackendSpec(kernel_backend, "pallas", flavor)
    warnings.warn(
        "kernel_backend='pallas' requested but no compiled Pallas lowering "
        f"exists on backend {jax.default_backend()!r}; falling back to "
        "interpret mode (correct but slow — use kernel_backend='auto' to "
        "prefer the XLA twin on such hosts)",
        stacklevel=2)
    return BackendSpec(kernel_backend, "interpret", None)
