"""Config system: model / train / elastic / shape / mesh dataclasses + registry.

Every assigned architecture gets one module in ``repro.configs`` exporting
``CONFIG: ModelConfig`` (exact public numbers, cited) and ``SMOKE: ModelConfig``
(reduced same-family variant for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

import ml_dtypes  # noqa: F401 — registers bfloat16 & co. with numpy
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | rwkv | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    d_ff: int
    vocab_size: int
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    # norms / activations
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    act: str = "swiglu"  # swiglu | gelu | geglu
    qk_norm: bool = False
    # rope
    rope_mode: str = "standard"  # standard | mrope | none
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    mrope_sections: Tuple[int, ...] = ()  # head_dim/2 split for (t, h, w)
    # attention locality
    sliding_window: Optional[int] = None
    attention_chunk: Optional[int] = None  # llama4-style chunked causal
    # embeddings
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    top_k: int = 1
    num_shared_experts: int = 0
    expert_d_ff: Optional[int] = None
    capacity_factor: float = 1.25
    first_dense_layers: int = 0
    router_aux_weight: float = 0.01
    # SSM / hybrid (zamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_head_dim: int = 64
    attn_every: int = 0  # hybrid: shared attn block every N ssm layers
    # rwkv
    rwkv_head_dim: int = 64
    # enc-dec
    enc_layers: int = 0
    dec_layers: int = 0
    enc_seq_ratio: int = 8  # decoder_len / encoder_len for shape derivation
    # modality stubs
    frontend: Optional[str] = None  # 'audio' | 'vision' | None
    num_patch_tokens: int = 0  # vlm: patch embeddings prepended per sample
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # misc
    source: str = ""  # citation
    # pallas kernels on/off (TPU path) for model-internal kernels (flash
    # attention). Inside an ElasticSession, RunSpec.use_pallas is the single
    # source of truth: the session coerces this field to match the spec, so
    # one flag drives both the model and the trainer kernel paths (ISSUE-7).
    use_pallas: bool = False
    # sequence-mix chunk size for SSD/RWKV chunked scans
    scan_chunk: int = 256

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def adtype(self):
        return np.dtype(self.dtype)

    @property
    def pdtype(self):
        return np.dtype(self.param_dtype)

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def e_dff(self) -> int:
        return self.expert_d_ff or self.d_ff

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# Failure scenario catalogue (generators live in repro/core/scenarios.py;
# kept here so ElasticConfig can validate without a circular import).
# "hetero" (persistent per-slot speeds) and "byzantine" (corrupt-gradient
# slots) are adversarial extensions beyond the paper's §VI fault model;
# trace replay is deliberately NOT in this catalogue — a recorded trace is
# loaded with `TraceScenario`/`read_trace` and attached via
# `RunSpec.schedule` (CLI: `--trace`), since it carries its own
# rounds/capacity and ignores the generator knobs below.
FAILURE_SCENARIOS = ("iid", "burst", "correlated", "straggler",
                     "crash_restart", "hetero", "byzantine")

# Membership scenario catalogue (planned worker-pool resize streams; the
# generators live next to the failure scenarios in repro/core/scenarios.py).
MEMBERSHIP_SCENARIOS = ("static", "scale_up", "scale_down",
                        "preempt_rejoin", "plan")


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Paper Section V hyper-parameters."""

    num_workers: int = 4
    # Worker-pool capacity (ISSUE-5). Every device-side worker-axis array is
    # sized at `cap` slots and an active mask selects the live ones, so
    # membership (join/leave/resize) can change between chunks with zero
    # recompiles — shapes are fixed at capacity. 0 means "exactly
    # num_workers" (the pre-elastic fixed-k regime, masking-free when the
    # membership scenario is static).
    capacity: int = 0
    tau: int = 1                      # communication period
    alpha: float = 0.1                # EASGD moving rate (best grid value, §VII)
    score_window: int = 5             # p most-recent u values kept (p-1 diffs)
    score_weights: Tuple[float, ...] = (0.5, 0.25, 0.15, 0.10)  # c_0 (newest) .. c_{p-2}
    score_k: float = -0.05            # threshold k < 0 in h1/h2
    overlap_ratio: float = 0.25       # r = o/n (paper: .25 @ k=4, .125 @ k=8)
    failure_prob: float = 1.0 / 3.0   # comm suppressed 1/3 of the time (§VI)
    dynamic: bool = True              # False → fixed-α EASGD behaviour
    oracle: bool = False              # EAHES-OM: oracle failure knowledge
    # Communication backend. "sequential" preserves the paper's event-ordered
    # single-device simulation (lax.scan over workers, master updated between
    # workers). "fused" batches all k syncs: one vmapped scoring pass and one
    # multi-worker elastic kernel; the master reduction uses the exact
    # event-order-equivalent weights, workers sync against the round-start
    # master (delayed averaging à la DaSGD).
    comm_mode: str = "sequential"     # sequential | fused
    # Delayed averaging depth (DaSGD; ISSUE-7). 0 = sync against the
    # round-start master (today's fused semantics, bit-exact with the
    # pre-staleness trajectories). 1 = workers score and pull toward the
    # *previous* round's master snapshot (``master_prev``), so round r's
    # elastic exchange depends only on state known before round r−1's
    # master reduction lands — the comm phase of round r can overlap the
    # local phase of round r+1. Fused-mode only: the sequential backend is
    # the paper's event-ordered live-master scan, where staleness has no
    # consistent meaning.
    staleness: int = 0                # 0 | 1
    # Execution placement (repro/core/coordinator.py). "single" simulates all
    # k workers on one device (vmap over the worker axis). "sharded" places
    # the worker axis over the mesh's 'pod' axis via shard_map: the local
    # phase runs fully parallel per shard and the fused comm phase scores
    # per-shard, reducing into the master with an event-order-equivalent
    # cross-pod collective. Requires comm_mode="fused" — the sequential
    # backend is an event-ordered scan over workers (each sync reads the
    # master the previous worker just wrote) and cannot shard.
    placement: str = "single"         # single | sharded
    # Failure scenario engine (repro/core/scenarios.py). "iid" is the paper's
    # Bernoulli model; the other regimes reuse failure_prob as their
    # stationary fault rate plus the knobs below.
    failure_scenario: str = "iid"
    burst_recover_prob: float = 0.25  # burst/straggler: P(bad→good)/round
    fault_groups: int = 2             # correlated: number of co-failing racks
    crash_downtime: int = 3           # crash_restart: rounds down per crash
    straggler_tau_scale: float = 0.5  # straggler: fraction of τ it completes
    # "hetero": persistent per-slot speed distribution. Each slot draws one
    # speed in (0, 1] at schedule time and keeps it for the whole run; the
    # local phase gives slot i max(1, round(speed_i * tau)) steps per round
    # (distinct from transient straggler masks, which also stale the score).
    hetero_dist: str = "lognormal"    # lognormal | bimodal
    hetero_sigma: float = 0.6         # lognormal: speed = min(1, exp(sigma·z))
    hetero_slow_frac: float = 0.25    # bimodal: P(slot is slow)
    hetero_slow_scale: float = 0.25   # bimodal: speed of slow slots
    # "byzantine": persistent corrupt-gradient slots. Each slot is byzantine
    # with prob byzantine_frac (at least one slot stays honest); honest slots
    # still suffer iid comm failures at failure_prob, so the corrupt and fail
    # masks are disjoint by construction. The coordinator applies the
    # corruption to gradients inside the jitted local phase.
    byzantine_frac: float = 0.25      # P(slot is corrupt) — persistent
    byzantine_mode: str = "sign_flip"  # sign_flip | scale | noise
    byzantine_scale: float = 5.0      # scale factor / noise std
    # Robustness clamp for dynamic weighting (beyond-paper; see
    # docs/paper_map.md deviation #10). The paper's h2 map gives *full*
    # weight alpha to any worker whose score is positive — including a
    # byzantine slot running away from the master — so a diverging poisoned
    # worker pollutes the master at the same rate as a healthy one. With
    # score_clip > 0, the master refuses the pull (w2 = 0) from any worker
    # whose raw score exceeds +score_clip. 0 disables the clamp and is
    # bit-identical to the paper's maps. Applies to both comm backends
    # (the clamp lives in dynamic_weight.weights_for).
    score_clip: float = 0.0
    # Absolute-distance containment (beyond-paper; ROADMAP item 5 /
    # docs/paper_map.md deviation #10). score_clip clamps the distance
    # *trend*, so an attack that parks a worker at a huge-but-static
    # distance (noise-mode corruption under AdaHessian's
    # curvature-normalized steps) has a raw score ≈ 0 and sails under the
    # clip. With u_zclip > 0 the master additionally refuses (w2 = 0) any
    # worker whose log-distance u sits more than u_zclip robust z-scores
    # (median / 1.4826·MAD) above the live pool's u distribution — a
    # cross-sectional term, so it lives in the batched scoring paths
    # (fused + hierarchical comm; the sequential scan computes u one
    # worker at a time against an evolving master and has no pool
    # snapshot to stand on). 0 disables it, bit-identically.
    u_zclip: float = 0.0
    # Hierarchical elastic averaging (tree-EASGD; the extension §VI of
    # Zhang et al.'s EASGD sketches and this repo builds). The
    # capacity-padded worker axis is partitioned into `groups` contiguous
    # rack-sized groups, each owning a *sub-master*: workers
    # elastic-average against their group's sub-master every round (τ
    # local steps), and the sub-masters elastic-average against the
    # global master every `global_period` rounds (τ_g = global_period·τ)
    # with their own h1/h2 dynamic weights — a dead rack is down-weighted
    # at the global level exactly as a dead worker is at the rack level.
    # groups=1, global_period=1 is the flat topology (sub-master ≡
    # master, bit-exact with the non-hierarchical fused coordinator).
    # Requires comm_mode="fused" when non-trivial.
    groups: int = 1
    global_period: int = 1
    # Membership scenario engine (repro/core/scenarios.py): a planned
    # (rounds, capacity) active-mask stream riding alongside the failure
    # masks. "static" keeps the initial num_workers slots live; scale_up /
    # scale_down resize the pool once at membership_round; preempt_rejoin
    # takes membership_k workers out for crash_downtime rounds; "plan" runs
    # the explicit (round, k) resize steps in membership_plan.
    membership_scenario: str = "static"
    membership_k: int = 0             # resize target / preempted count (0 = scenario default)
    membership_round: int = 0         # when the membership event fires (0 = rounds//2)
    membership_plan: Tuple[Tuple[int, int], ...] = ()  # "plan": (round, k) steps

    @property
    def cap(self) -> int:
        """Padded worker-axis length: ``capacity`` slots (>= num_workers),
        or exactly ``num_workers`` when capacity is left at 0."""
        return self.capacity or self.num_workers

    @property
    def hierarchical(self) -> bool:
        """True when the two-level coordinator is non-trivially configured
        (more than one rack, or an amortized global sync period). The
        trivial (1, 1) topology runs the flat coordinator — bit-exactly —
        unless a trainer forces the hierarchical state on for proofs."""
        return self.groups > 1 or self.global_period > 1

    def __post_init__(self):
        if self.comm_mode not in ("sequential", "fused"):
            raise ValueError(
                f"comm_mode must be 'sequential' or 'fused', "
                f"got {self.comm_mode!r}")
        if self.placement not in ("single", "sharded"):
            raise ValueError(
                f"placement must be 'single' or 'sharded', "
                f"got {self.placement!r}")
        if self.placement == "sharded" and self.comm_mode != "fused":
            raise ValueError(
                "placement='sharded' requires comm_mode='fused': the "
                "sequential backend is an event-ordered scan over workers "
                "and cannot be placed on disjoint mesh shards")
        if self.staleness not in (0, 1):
            raise ValueError(
                f"staleness must be 0 or 1, got {self.staleness!r}")
        if self.staleness and self.comm_mode != "fused":
            raise ValueError(
                "staleness=1 (delayed averaging) requires comm_mode='fused':"
                " the sequential backend is the paper's event-ordered scan "
                "against the live master, where a stale sync target has no "
                "consistent meaning")
        if self.failure_scenario not in FAILURE_SCENARIOS:
            raise ValueError(
                f"failure_scenario must be one of {FAILURE_SCENARIOS}, "
                f"got {self.failure_scenario!r}")
        if self.num_workers < 1:
            raise ValueError(
                f"num_workers must be >= 1, got {self.num_workers}")
        if self.capacity and self.capacity < self.num_workers:
            raise ValueError(
                f"capacity={self.capacity} must be >= "
                f"num_workers={self.num_workers} (capacity pads the worker "
                "axis; it cannot truncate the initial membership)")
        if self.hetero_dist not in ("lognormal", "bimodal"):
            raise ValueError(
                f"hetero_dist must be 'lognormal' or 'bimodal', "
                f"got {self.hetero_dist!r}")
        if self.hetero_sigma <= 0:
            raise ValueError(
                f"hetero_sigma must be > 0, got {self.hetero_sigma}")
        if not 0.0 <= self.hetero_slow_frac <= 1.0:
            raise ValueError(
                f"hetero_slow_frac must be in [0, 1], "
                f"got {self.hetero_slow_frac}")
        if not 0.0 < self.hetero_slow_scale <= 1.0:
            raise ValueError(
                f"hetero_slow_scale must be in (0, 1], "
                f"got {self.hetero_slow_scale}")
        if not 0.0 <= self.byzantine_frac < 1.0:
            raise ValueError(
                f"byzantine_frac must be in [0, 1) — at least one slot "
                f"must stay honest — got {self.byzantine_frac}")
        if self.byzantine_mode not in ("sign_flip", "scale", "noise"):
            raise ValueError(
                f"byzantine_mode must be 'sign_flip', 'scale' or 'noise', "
                f"got {self.byzantine_mode!r}")
        if self.byzantine_scale <= 0:
            raise ValueError(
                f"byzantine_scale must be > 0, got {self.byzantine_scale}")
        if self.score_clip < 0:
            raise ValueError(
                f"score_clip must be >= 0 (0 disables the clamp), "
                f"got {self.score_clip}")
        if self.u_zclip < 0:
            raise ValueError(
                f"u_zclip must be >= 0 (0 disables the absolute-distance "
                f"containment), got {self.u_zclip}")
        if self.groups < 1:
            raise ValueError(f"groups must be >= 1, got {self.groups}")
        if self.global_period < 1:
            raise ValueError(
                f"global_period must be >= 1, got {self.global_period}")
        if self.groups > self.cap:
            raise ValueError(
                f"groups={self.groups} exceeds the worker capacity "
                f"{self.cap} — a rack needs at least one slot")
        if self.hierarchical and self.comm_mode != "fused":
            raise ValueError(
                "hierarchical averaging (groups > 1 or global_period > 1) "
                "requires comm_mode='fused': the group sync reuses the "
                "batched scoring + event-order-equivalent reduction, and "
                "the sequential backend's serial master dependency has no "
                "per-rack meaning")
        if self.hierarchical and self.staleness:
            raise ValueError(
                "hierarchical averaging does not compose with staleness=1 "
                "(delayed averaging references the previous global master; "
                "under a hierarchy the workers' sync target is their "
                "sub-master, which has no one-round-stale snapshot)")
        if self.membership_scenario not in MEMBERSHIP_SCENARIOS:
            raise ValueError(
                f"membership_scenario must be one of {MEMBERSHIP_SCENARIOS},"
                f" got {self.membership_scenario!r}")
        if self.membership_scenario == "plan" and not self.membership_plan:
            raise ValueError(
                "membership_scenario='plan' needs a non-empty "
                "membership_plan of (round, k) steps")
        for step in self.membership_plan:
            r, k = step
            if r < 0 or not 1 <= k <= self.cap:
                raise ValueError(
                    f"membership_plan step {step}: need round >= 0 and "
                    f"1 <= k <= capacity ({self.cap})")


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adahessian"  # sgd | momentum | adam | adahessian
    lr: float = 0.01
    momentum: float = 0.5
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    hutchinson_samples: int = 1
    spatial_block: int = 128   # spatial-averaging block on last dim
    hessian_power: float = 1.0
    # Beyond-paper (§Perf): refresh the Hutchinson diagonal every h steps
    # (curvature moves slowly; AdaHessian's own delayed-Hessian discussion).
    # 1 = paper-faithful (every step).
    hessian_every: int = 1


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    remat: str = "none"  # none | full | dots
    seed: int = 0
    log_every: int = 10


ARCH_IDS = (
    "zamba2_7b",
    "llama4_scout_17b_a16e",
    "stablelm_3b",
    "h2o_danube_1_8b",
    "seamless_m4t_large_v2",
    "qwen3_4b",
    "mixtral_8x22b",
    "qwen2_vl_7b",
    "moonshot_v1_16b_a3b",
    "rwkv6_3b",
)

# CLI ids (hyphenated, as assigned) -> module names
ARCH_ALIASES = {
    "zamba2-7b": "zamba2_7b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "stablelm-3b": "stablelm_3b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "qwen3-4b": "qwen3_4b",
    "mixtral-8x22b": "mixtral_8x22b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "rwkv6-3b": "rwkv6_3b",
    "paper-cnn": "paper_cnn",
}


def normalize_arch(arch: str) -> str:
    return ARCH_ALIASES.get(arch, arch.replace("-", "_").replace(".", "_"))


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{normalize_arch(arch)}")
    return mod.SMOKE if smoke else mod.CONFIG


def list_archs():
    return list(ARCH_IDS)


# long_500k eligibility (see DESIGN.md §Arch-applicability): sub-quadratic
# or windowed-context architectures only.
LONG_CONTEXT_OK = {
    "zamba2_7b",
    "rwkv6_3b",
    "h2o_danube_1_8b",
    "mixtral_8x22b",
    "llama4_scout_17b_a16e",
}


def shape_supported(arch: str, shape: str) -> bool:
    arch = normalize_arch(arch)
    if shape == "long_500k":
        return arch in LONG_CONTEXT_OK
    return True
