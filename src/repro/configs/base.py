"""Config system: architecture configs, input shapes, FL/NOMA system config.

Every assigned architecture from the public pool gets one module in this
package defining ``CONFIG = ModelConfig(...)`` with the exact assigned
hyper-parameters (source cited in brackets in each file). ``get_config``
resolves ``--arch <id>`` strings.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Tuple

# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Transformer-family architecture description.

    ``family`` selects the assembly in ``repro.models.zoo``:
      dense | moe | ssm | hybrid | encdec | vlm
    """

    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int            # query heads (0 for attention-free archs)
    n_kv_heads: int         # GQA KV heads
    d_ff: int               # per-expert FF width for MoE archs
    vocab_size: int
    head_dim: int = 0       # 0 -> d_model // n_heads

    # --- MoE (dropless; models/moe.py, DESIGN.md section 3) ---
    n_experts: int = 0      # routed experts the router scores
    top_k: int = 0
    router: str = "softmax"  # softmax: top-k of softmax, renormalised |
                             # sigmoid: top-k of sigmoid + bias (noaux_tc)
    routed_scale: float = 1.0   # sigmoid router's routed_scaling_factor
    experts_held: int = 0       # routed experts held here (0 = all): an
    first_held_expert: int = 0  # expert-parallel share [first, first+held)
    n_shared_experts: int = 0   # shared experts: one SwiGLU of width
                                # n_shared_experts * d_ff, every token
    first_dense_layers: int = 0  # leading dense layers (first_k_dense_replace)
    dense_d_ff: int = 0          # their SwiGLU width

    # --- multi-head latent attention (MLA; 0 = standard attention) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- SSM / RWKV / hybrid ---
    ssm_state: int = 0      # mamba-style per-channel state size
    rwkv_head_size: int = 0  # rwkv6 head size (64 in Finch)

    # --- attention details ---
    rope_frac: float = 1.0        # fraction of head_dim with rotary applied
    rope_theta: float = 10_000.0
    sliding_window: int = 0       # 0 = full attention (train/prefill/decode_32k)
    long_context_window: int = 8192   # SWA window used for long_500k decode
    parallel_residual: bool = False   # stablelm/gpt-neox style
    glu: bool = True                  # gated MLP (swiglu) vs plain gelu MLP
    qkv_bias: bool = False
    logit_softcap: float = 0.0        # grok-style logit soft-capping

    # --- encoder-decoder (audio) ---
    n_enc_layers: int = 0

    # --- multimodal stubs ---
    n_prefix_tokens: int = 0      # vlm: image patch tokens; audio: enc frames
    prefix_dim: int = 0           # embedding dim of stub frontend output

    # --- numerics / training ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    def __post_init__(self) -> None:
        if self.kv_lora_rank:
            # MLA: query/key heads carry the nope and rope parts
            object.__setattr__(self, "head_dim", self.qk_nope_head_dim
                               + self.qk_rope_head_dim)
        elif self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r} "
                             "(expected 'softmax' or 'sigmoid')")
        if self.n_experts and not (
                0 <= self.first_held_expert
                and self.first_held_expert + self.n_held <= self.n_experts):
            raise ValueError(
                f"held experts [{self.first_held_expert}, "
                f"{self.first_held_expert + self.n_held}) lie outside the "
                f"{self.n_experts} routed experts")

    # -- derived ----------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 16 so the embedding/lm_head
        always shard over the 16-way model axis (hymba 32001, seamless
        256206 are otherwise unshardable -> replicated logits). Padded
        logits are masked to -inf in unembed."""
        return self.vocab_size + (-self.vocab_size) % 16

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this model holds."""
        return self.experts_held or self.n_experts

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def v_dim(self) -> int:
        """Per-head value width."""
        return self.v_head_dim or self.head_dim

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Natively supports 500k decode without a full KV cache."""
        return self.family in ("ssm", "hybrid")

    def attn_param_count(self) -> int:
        """Weights of one attention sub-block (its norms not counted)."""
        d, h = self.d_model, self.n_heads
        if self.is_mla:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            return (d * h * self.head_dim + d * (r + rope) + r
                    + r * h * (self.qk_nope_head_dim + self.v_dim)
                    + h * self.v_dim * d)
        q, kv = h * self.head_dim, self.n_kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d

    def _mlp_params(self, width: int) -> int:
        return self.d_model * width * (3 if self.glu else 2)

    def _moe_layer_params(self, held: int) -> int:
        """Router (and the sigmoid router's bias), ``held`` routed experts
        and the shared experts of one MoE layer."""
        e = self.n_experts
        return (self.d_model * e + (e if self.router == "sigmoid" else 0)
                + held * self._mlp_params(self.d_ff)
                + self._mlp_params(self.n_shared_experts * self.d_ff))

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head, RMSNorm
        scales included); a MoE layer counts the experts held here."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        emb = v * d
        head = 0 if self.tie_embeddings else v * d
        blocks = 0
        n_dec = self.n_layers
        hd = self.head_dim
        for i in range(n_dec):
            blk = 2 * d  # ln1, ln2
            if self.family == "ssm":  # rwkv6: time-mix + channel-mix
                blk += 4 * d * d + d * d  # r,k,v,o + gate
                blk += d * ff + ff * d    # channel mix (k, v)
            else:
                blk += self.attn_param_count()
                if self.family == "hybrid":
                    blk += 2 * d * d + d * self.ssm_state * 2  # ssm branch approx
                if self.is_moe and i < self.first_dense_layers:
                    blk += self._mlp_params(self.dense_d_ff)
                elif self.is_moe:
                    blk += self._moe_layer_params(self.n_held)
                else:
                    blk += self._mlp_params(ff)
            blocks += blk
        enc = 0
        for _ in range(self.n_enc_layers):
            q = self.n_heads * hd
            kv = self.n_kv_heads * hd
            enc += d * q + 2 * d * kv + q * d
            enc += d * ff * (3 if self.glu else 2)
            # decoder cross-attention counted per decoder layer
        cross = self.n_enc_layers and n_dec * (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d)
        return emb + head + blocks + d + enc + (cross or 0)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of the n_experts routed
        experts, so top_k * held / n_experts of those held here)."""
        if not self.is_moe:
            return self.param_count()
        n_moe = self.n_layers - self.first_dense_layers
        mlp = self._mlp_params(self.d_ff)
        inactive = n_moe * mlp * (self.n_held - self.top_k * self.n_held
                                  / self.n_experts)
        return self.param_count() - int(round(inactive))

    # -- reduced variant for CPU smoke tests ------------------------------
    def reduced(self) -> "ModelConfig":
        """Same family/topology, shrunk to laptop scale (<=512 d_model,
        2 layers, <=4 experts) for the per-arch smoke tests."""
        d = min(self.d_model, 128)
        if self.n_heads:
            g = max(1, self.n_heads // max(self.n_kv_heads, 1))
            kv = 1 if g > 1 else 2
            n_heads = kv * min(g, 4)
            hd = 16
        else:
            n_heads = kv = hd = 0
        mla = {}
        if self.is_mla:
            kv = n_heads   # MLA: one key/value per query head
            mla = dict(kv_lora_rank=min(self.kv_lora_rank, 32),
                       qk_nope_head_dim=16, qk_rope_head_dim=8,
                       v_head_dim=16)
        return dataclasses.replace(
            self,
            n_layers=2,
            d_model=d,
            n_heads=n_heads,
            n_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 4 * d),
            vocab_size=min(self.vocab_size, 512),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            experts_held=0, first_held_expert=0,
            first_dense_layers=min(self.first_dense_layers, 1),
            dense_d_ff=min(self.dense_d_ff, 4 * d),
            n_enc_layers=2 if self.n_enc_layers else 0,
            n_prefix_tokens=min(self.n_prefix_tokens, 8) if self.n_prefix_tokens else 0,
            prefix_dim=d if self.prefix_dim else 0,
            rwkv_head_size=min(self.rwkv_head_size, 16) if self.rwkv_head_size else 0,
            long_context_window=256,
            dtype="float32",
            **mla,
        )


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# FL + NOMA system config (the paper)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NOMAConfig:
    """Uplink NOMA cell parameters. [ASSUMED] values follow the standard
    FL-over-wireless simulation genre (see DESIGN.md section 4)."""

    n_subchannels: int = 5          # K
    users_per_subchannel: int = 2   # J (power-domain NOMA pair)
    bandwidth_hz: float = 1e6       # B per subchannel
    noise_density: float = 1e-20    # N0 (W/Hz) ~ -170 dBm/Hz
    max_power_w: float = 0.2        # P_max per client (23 dBm)
    path_loss_exp: float = 3.76
    ref_path_loss: float = 1e-3     # at 1 m
    cell_radius_m: float = 500.0
    min_radius_m: float = 50.0
    sic_order: str = "strong_first"  # uplink SIC: strongest decoded first


# Canonical axis registries. Declared here so FLConfig can validate
# eagerly without importing the implementing subsystems (configs must
# stay import-leaf); every module takes them from here.

# engine admission-stage implementations (core/plan.resolve_admission;
# DESIGN.md section 9)
ADMISSIONS = ("auto", "full_sort", "segmented")

# multi-cell base-station layouts (sim/topology.py, DESIGN.md section 10)
CELL_LAYOUTS = ("hex", "grid")

# selection/RA policies (fl/server.py FLServer.select, engine priorities)
POLICIES = ("age_noma", "age_noma_budget", "random", "channel",
            "round_robin", "oma_age")
# the policies whose priority is the paper's age key (core/engine.py
# policy_priority); of them, BUDGET_POLICY plans under a round-time budget
# and OMA_POLICY gives each admitted client a subchannel of its own
AGE_POLICIES = ("age_noma", "age_noma_budget", "oma_age")
BUDGET_POLICY = "age_noma_budget"
OMA_POLICY = "oma_age"

# subchannel pairing policies (core/pairing.py, DESIGN.md section 7)
PAIRINGS = ("strong_weak", "adjacent", "hungarian", "greedy_matching")

# admitted-set selection modes (core/plan.py, DESIGN.md section 8)
SELECTIONS = ("greedy_set", "joint")

# scheduling engines (core/scheduler.py fp64 reference | core/engine.py)
ENGINES = ("numpy", "jax")

# kernel lowering backends for the jax engine's Pallas kernels
# (kernels/backend.py resolve_backend; DESIGN.md section 13):
#   auto            compiled Pallas when the host can lower it (Mosaic on
#                   TPU, Triton on GPU), else the XLA twin
#   xla             pure-jnp twin always
#   pallas          compiled Pallas, interpret fallback on CPU/CI hosts
#   pallas_interpret interpret mode unconditionally (correctness oracle)
KERNEL_BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")

# server-side update predictors for unselected clients (fl/predictor.py)
PREDICTORS = ("none", "stale", "ann")

# every planner choice with its registry: FLConfig and each per-call
# override check a value against it through check_choice
CHOICES = {"policy": POLICIES, "engine": ENGINES, "pairing": PAIRINGS,
           "selection": SELECTIONS, "admission": ADMISSIONS,
           "cell_layout": CELL_LAYOUTS, "kernel_backend": KERNEL_BACKENDS,
           "predictor": PREDICTORS}


def check_choice(field: str, value: str) -> str:
    """``value`` if it names one of ``field``'s choices (``CHOICES``), else
    ValueError — the one check of a planner choice, whether it comes from
    an ``FLConfig`` or a per-call override."""
    registry = CHOICES[field]
    if value not in registry:
        raise ValueError(f"unknown {field} {value!r} "
                         f"(expected one of {registry})")
    return value

# FLConfig fields exempt from __post_init__ validation (reprolint
# config-validation rule): each entry names WHY eager checking is
# impossible or meaningless here, not merely unimplemented.
_POST_INIT_EXEMPT = (
    "scenario",       # registry lives in sim/scenario.py (not import-leaf);
                      # get_scenario_config raises the eager ValueError with
                      # the registered names at resolution
    "seed",           # any int is a valid PRNG seed
)


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_clients: int = 50
    rounds: int = 100
    local_epochs: int = 1
    local_batch: int = 32
    lr: float = 0.05
    momentum: float = 0.0
    dirichlet_alpha: float = 0.5     # non-IID level
    samples_per_client: Tuple[int, int] = (200, 1200)  # min/max, uniform
    # scheduler
    policy: str = "age_noma"         # POLICIES above
    age_exponent: float = 1.0        # gamma
    t_budget_s: float = 0.0          # 0 = no budget (pure min-round-time)
    engine: str = "numpy"            # numpy (fp64 reference) | jax (batched
                                     # core.engine path for the age policies)
    # kernel lowering backend for the jax engine's Pallas kernels
    # (KERNEL_BACKENDS above; kernels/backend.py resolves it against the
    # host's actual lowering capability at engine construction)
    kernel_backend: str = "auto"
    # subchannel pairing policy (core/pairing.py, DESIGN.md section 7):
    #   strong_weak     i-th strongest with i-th weakest (paper heuristic)
    #   adjacent        neighbouring sorted gains (NOMA worst-case ablation)
    #   hungarian       min-sum assignment on the pair completion-time table
    #                   (never slower than strong_weak by construction)
    #   greedy_matching greedy max-score pairs on the effective-power
    #                   score table (precision-stable min-rate surrogate)
    pairing: str = "strong_weak"
    # admitted-set selection mode (core/plan.py, DESIGN.md section 8):
    #   greedy_set  top-slots clients by (priority, gain, index) — the
    #               paper's sequential select-then-pair pipeline
    #   joint       pairing-aware admission: the set whose best matching
    #               minimizes round time (exhaustive on |N| <= 8, swap/prune
    #               local search above; never slower than greedy_set per
    #               round by construction)
    selection: str = "greedy_set"
    # admission-stage implementation of the jax engine (core/engine.py,
    # DESIGN.md section 9) — a pure performance knob, the admitted set is
    # bit-for-bit identical either way:
    #   auto        full_sort below plan.ADMISSION_AUTO_N clients,
    #               segmented at or above (the measured crossover)
    #   full_sort   population-wide bitonic threshold sorts (small N)
    #   segmented   exact bit-space threshold search + candidate-only
    #               sorts, O(N) in the population (large N)
    admission: str = "auto"
    # multi-cell topology (sim/topology.py, DESIGN.md section 10): n_cells
    # base stations laid out on a hex spiral or square grid with spacing
    # sqrt(3) * cell_radius_m; clients associate with the nearest BS every
    # round (mobility across a boundary = handover, age state follows the
    # client) and each cell runs the staged planner on its own K subchannels
    # (frequency reuse 1). n_cells=1 is bitwise the single-cell planner.
    n_cells: int = 1
    cell_layout: str = "hex"
    # wireless environment dynamics (repro.sim registry: static_iid |
    # pedestrian | vehicular | iot_bursty | hotspot_shadowed)
    scenario: str = "static_iid"
    # client compute model
    cpu_cycles_per_sample: float = 2e6
    cpu_freq_range_ghz: Tuple[float, float] = (0.5, 2.0)
    model_bits: float = 0.0          # 0 = derived from model param count * 32
    # server-side update predictor for unselected clients (paper Sec. V ANN;
    # see repro.fl.predictor for the blend formula)
    predictor: str = "none"          # none | stale | ann
    pred_embed_dim: int = 32         # count-sketch dim fed to the ANN
    pred_hidden_dim: int = 64        # MLP hidden width
    pred_lr: float = 1e-2            # online Adam lr
    pred_steps: int = 8              # optimizer steps per round
    pred_discount: float = 0.7       # rho: age discount of predicted updates
    pred_blend: float = 0.5          # beta: trust of predicted vs received
    pred_max_age: int = 0            # only predict clients with A_n <= this
                                     # (0 = no staleness cap)
    seed: int = 0

    def __post_init__(self) -> None:
        # fail at construction, not deep inside a Monte-Carlo sweep; the
        # per-call overrides go through the same check_choice. Every field
        # is checked here or listed in _POST_INIT_EXEMPT with a reason
        # (enforced by the reprolint config-validation rule).
        for field in ("policy", "engine", "pairing", "selection",
                      "admission", "cell_layout", "kernel_backend",
                      "predictor"):
            check_choice(field, getattr(self, field))
        for field in ("n_clients", "rounds", "local_epochs", "local_batch",
                      "pred_embed_dim", "pred_hidden_dim", "pred_steps"):
            if getattr(self, field) < 1:
                raise ValueError(f"{field} must be >= 1, "
                                 f"got {getattr(self, field)}")
        for field in ("lr", "dirichlet_alpha", "cpu_cycles_per_sample",
                      "pred_lr"):
            if getattr(self, field) <= 0:
                raise ValueError(f"{field} must be > 0, "
                                 f"got {getattr(self, field)}")
        for field in ("age_exponent", "t_budget_s", "model_bits",
                      "momentum", "pred_max_age"):
            if getattr(self, field) < 0:
                raise ValueError(f"{field} must be >= 0, "
                                 f"got {getattr(self, field)}")
        for field in ("pred_discount", "pred_blend"):
            if not 0.0 <= getattr(self, field) <= 1.0:
                raise ValueError(f"{field} must be in [0, 1], "
                                 f"got {getattr(self, field)}")
        lo, hi = self.samples_per_client
        if not 1 <= lo <= hi:
            raise ValueError(f"samples_per_client must satisfy "
                             f"1 <= min <= max, got {(lo, hi)}")
        flo, fhi = self.cpu_freq_range_ghz
        if not 0 < flo <= fhi:
            raise ValueError(f"cpu_freq_range_ghz must satisfy "
                             f"0 < min <= max, got {(flo, fhi)}")
        if self.n_cells < 1:
            raise ValueError(f"n_cells must be >= 1, got {self.n_cells}")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "moonshot_v1_16b_a3b",
    "llama4_maverick_400b_a17b",
    "paligemma_3b",
    "hymba_1_5b",
    "seamless_m4t_medium",
    "stablelm_1_6b",
    "chatglm3_6b",
    "smollm_135m",
    "rwkv6_7b",
    "grok_1_314b",
]


def canon(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(arch)}")
    return mod.CONFIG


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
