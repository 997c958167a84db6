"""Config system for the framework.

Everything a run needs is described by frozen dataclasses:

  ModelConfig  — architecture (one per assigned arch in ``repro.configs``)
  DPConfig     — differential-privacy knobs (paper Eqs. 10–12)
  P4Config     — the paper's technique: grouping + proxy/private co-training
  MeshConfig   — device mesh (single-pod / multi-pod)
  ScheduleConfig — round schedule (full / sampling / async) + DP accounting
  TopologyConfig — P2P communication graph + mixing weights + link faults
  KernelConfig — Pallas/jnp kernel backend selection + autotuning
  TrainConfig  — optimizer/schedule/steps
  RunConfig    — the composed top-level config consumed by launch scripts

Configs are plain dataclasses (no framework dependency) so they can be
constructed programmatically, overridden from the CLI (``--arch``,
``--shape``, ``key=value`` dotted overrides) and serialized to JSON next to
checkpoints.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Architecture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0            # 0 => dense MLP
    experts_per_token: int = 0      # top-k
    aux_loss_weight: float = 0.01   # router load-balance loss
    shared_expert: bool = False     # llama4-style shared expert alongside routed
    capacity_factor: float = 0.0    # 0 => dense (masked einsum) dispatch
    dispatch: str = "global"        # "local" = per-data-shard dispatch (§Perf)
    # expert share (models/moe.py ``expert_share``): this chip holds experts
    # [held_first, held_first + held_count) of ``num_experts``; 0 => all of
    # them, under the capacity dispatch above
    held_first: int = 0
    held_count: int = 0


@dataclass(frozen=True)
class YarnConfig:
    """YaRN RoPE scaling (arXiv:2309.00071) as HF ``rope_parameters`` give it."""
    factor: float = 1.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0   # cos and sin are scaled by it


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) / xLSTM settings for ssm and hybrid architectures."""
    state_dim: int = 0              # N (per-head state size); 0 => no SSM
    num_heads: int = 0              # SSD heads (mamba2) / mLSTM heads
    head_dim: int = 0
    conv_width: int = 4             # causal depthwise conv width (mamba2)
    chunk_size: int = 128           # SSD chunked-scan block length
    expand: int = 2                 # d_inner = expand * d_model


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"           # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0               # 0 => d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    max_seq_len: int = 8192
    # attention flavour
    qk_norm: bool = False           # qwen3
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (t, h, w) split of head_dim/2
    window: int = 0                 # sliding-window size; 0 => full attention
    # per-layer attention types, one period scanned over the depth:
    # "sliding" layers use ``window``, "full" layers attend to every earlier
    # position and take ``yarn`` where it is set; () => every layer "sliding"
    # (full attention where ``window`` is 0)
    layer_pattern: Tuple[str, ...] = ()
    yarn: Optional[YarnConfig] = None
    # beyond-paper perf knob: pad query heads up to a mesh-divisible count so
    # attention shards over the model axis (e.g. qwen3/llama4: 40 -> 48).
    # Zero-extra-capacity heads: a strict superset model, HLO-validated in
    # EXPERIMENTS.md §Perf.
    pad_attn_heads_to: int = 0
    attn_logit_softcap: float = 0.0
    # hybrid layout (zamba2): attention block shared & interleaved every k mamba blocks
    hybrid_attn_every: int = 0      # 0 => homogeneous stack
    # xLSTM layout: pattern of block kinds, e.g. ("m","m","s") cycled
    xlstm_pattern: Tuple[str, ...] = ()
    # MoE / SSM subconfigs
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # multimodal stubs (brief carve-out: frontends provide embeddings)
    vision_tokens: int = 0          # qwen2-vl: number of patch embeddings per sample
    audio_codebooks: int = 0        # musicgen: EnCodec codebooks (delay pattern)
    # numerics
    dtype: str = "bfloat16"         # activation/compute dtype
    kv_cache_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    logits_dtype: str = "bfloat16"  # large-vocab logits kept in bf16, sharded
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # remat: "none" | "block" (checkpoint each scanned block) | "full"
    remat: str = "block"
    # --- cost-faithful lowering knobs (dry-run roofline extraction ONLY) ---
    # XLA cost_analysis counts while-loop bodies ONCE; the roofline pass
    # lowers with two unroll factors (1 and u) and extrapolates
    # total = f1 + (L-1)(fu - f1)/(u-1) to recover true per-step cost.
    unroll_layers: int = 1        # outer layer-stack scan unroll factor
    unroll_inner: bool = False    # fully unroll SSD/mLSTM chunk scans
    force_full_attention: bool = False
    # citation for the assigned-architecture table
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def validate(self) -> None:
        assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
            f"{self.name}: num_heads={self.num_heads} not divisible by "
            f"num_kv_heads={self.num_kv_heads}")
        if self.family == "moe" or self.moe.num_experts:
            assert self.moe.experts_per_token >= 1
        if self.family in ("ssm", "hybrid"):
            assert self.ssm.state_dim > 0 or self.xlstm_pattern


# ---------------------------------------------------------------------------
# The paper's technique
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DPConfig:
    """Differential privacy (paper §3.3 Phase 2, Eqs. 10–12)."""
    enabled: bool = True
    epsilon: float = 15.0           # paper's default target budget
    delta: float = 0.0              # 0 => 1/R, paper §4.1
    clip_norm: float = 1.0          # C
    # σ_g: 0 => derive from (ε, δ) via Eq. 12 (Noble et al. with l = M' = 1)
    noise_multiplier: float = 0.0
    sample_rate: float = 1.0        # s — data (batch) subsampling ratio
    local_steps: int = 1            # K — local steps between exchanges
    rounds: int = 100               # T — paper fixes T=100 communication rounds
    microbatches: int = 0           # 0 => exact per-example (vmap); k => scan over k
    # 0 => one vmap over the whole batch (B× parameter memory); c => scan over
    # B/c chunks of c vmapped examples — same per-example semantics, c× memory
    per_example_chunk: int = 0
    noise_router: bool = True       # MoE ablation knob (see DESIGN §4)


@dataclass(frozen=True)
class P4Config:
    """The paper's contribution as a first-class framework feature."""
    enabled: bool = True
    # Phase 1 — grouping
    group_size: int = 8             # T in Eq. 5 (paper: 8, CIFAR-100: 4)
    sample_peers: int = 35          # H — peers sampled for similarity (paper §4.5)
    similarity: str = "l1"          # paper metric (Eq. 3); "random" => ablation
    # Phase 2 — co-training
    alpha: float = 0.5              # Eq. 8 proxy   = (1-a) CE + a KL(w ‖ θ)
    beta: float = 0.5               # Eq. 9 private = (1-b) CE + b KL(θ ‖ w)
    distill_temperature: float = 1.0
    proxy_width_mult: float = 1.0   # <1 => width-reduced proxy (LM scale, DESIGN §4)
    aggregator_rotation: int = 1    # rounds between rotating the group aggregator
    handcrafted_features: bool = True  # ScatterNet frontend (ablation knob)
    manual_pod: bool = False        # shard_map the pod axis (XLA-version gated)


@dataclass(frozen=True)
class TopologyConfig:
    """Communication graph for the P2P layer (``repro.topology``).

    ``family="none"`` keeps each strategy's built-in pattern (DP-DSGT's
    ring, P4's group-internal mean). Any other family builds an explicit
    graph + doubly-stochastic mixing matrix: DP-DSGT gossips over it, P4
    routes its group messages along its shortest paths (per-link byte/hop
    accounting) and — with fault rates — drops member↔aggregator exchanges.
    """
    family: str = "none"    # none | ring | full | torus | kregular |
                            # exponential | erdos | smallworld | group | gossip
    k: int = 4              # degree (kregular / smallworld base lattice)
    p: float = 0.3          # erdos edge prob / smallworld rewire prob
    self_weight: float = 0.5   # lazy self weight for uniform weighting
    weighting: str = "metropolis"  # metropolis | uniform (regular graphs)
    drop_prob: float = 0.0  # per-round undirected-link failure probability
    churn_prob: float = 0.0  # per-round node-offline probability
    period: int = 8         # gossip family: matchings per cycle
    bridge: bool = True     # group family: ring bridge between groups
    seed: int = 0           # random-family construction seed
    # learned graphs (repro.topology.learned.GraphLearner): re-estimate the
    # collaboration graph from private pairwise model similarity every
    # learn_every rounds (0 = static graph), keeping learn_k out-neighbors
    # per client; each release adds Gaussian noise at learn_sigma × clip to
    # the measured distances and is charged to the PrivacyLedger
    learn_every: int = 0    # rounds between re-estimations (0 = off)
    learn_k: int = 0        # out-degree kept per client (0 => use k)
    learn_window: int = 1   # estimates folded as a TimeVaryingTopology
    learn_sigma: float = 1.0   # DP noise multiplier on released distances
    learn_temperature: float = 1.0  # similarity→trust softmax temperature


@dataclass(frozen=True)
class ScheduleConfig:
    """Round schedule + engine-native privacy accounting
    (``repro.engine.schedule`` / ``repro.engine.accounting``)."""
    kind: str = "full"              # full | sampling | async
    client_rate: float = 1.0        # q — per-round client participation
    mode: str = "bernoulli"         # sampling: bernoulli | fixed cohort
    staleness: int = 0              # async: rounds between buffered merges
    staleness_pow: float = 0.5      # async: merge weight (1+s)^-pow (FedBuff)
    accountant: str = "rdp"         # rdp | none — (ε, δ) ledger into History


@dataclass(frozen=True)
class FaultConfig:
    """Correlated fault processes (``repro.resilience``).

    Unlike ``TopologyConfig.drop_prob``/``churn_prob`` (i.i.d. per round),
    these are Markov transition rates: links fail in bursts (Gilbert–Elliott),
    nodes dwell in outages, partitions cut a sampled bisection for a random
    stretch of rounds, and stragglers stay slow until they recover. All rates
    zero = disabled (the engine's fault-free trace is untouched). When a
    process is enabled it supersedes the topology's i.i.d. rates.
    """
    link_fail: float = 0.0        # per-edge good→bad rate (bursty links)
    link_repair: float = 1.0      # bad→good; mean burst = 1/link_repair
    node_fail: float = 0.0        # node outage rate
    node_repair: float = 1.0      # mean outage = 1/node_repair rounds
    partition_prob: float = 0.0   # chance a bisection partition starts
    partition_repair: float = 0.5  # chance an active partition heals
    slow_enter: float = 0.0       # straggler chain: fast→slow
    slow_exit: float = 1.0        # slow→fast
    quorum: float = 0.0           # P4: min up-fraction for group aggregation


@dataclass(frozen=True)
class KernelConfig:
    """Kernel backend selection + autotuning (repro.kernels.dispatch).

    Replaces the old bare ``use_pallas: bool``: backend choice is a policy
    (compiled Pallas on TPU, jnp reference on CPU, interpreter only for
    explicit debugging), and tile sizes are autotuned per (shape, dtype,
    backend) rather than hardcoded.
    """
    backend: str = "auto"           # auto | pallas | interpret | ref
    autotune: bool = True           # tile-size search on first (shape, dtype)
    autotune_trials: int = 2        # timed repetitions per candidate
    # explicit tile overrides; (0, 0) => autotune (or kernel defaults)
    dp_clip_tile: Tuple[int, int] = (0, 0)    # (tb, td)
    l1_tile: Tuple[int, int] = (0, 0)         # (tm, td)
    dp_round_tile: int = 0                    # tf; 0 => autotune/default
    mix_halo_tile: int = 0                    # halo-mix row block; 0 => auto
                                              # (untiled unless tuned better)


# ---------------------------------------------------------------------------
# Distribution / run
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    pods: int = 2
    data: int = 16
    model: int = 16
    # client-mesh size for the sharded federation engine
    # (repro.engine.ShardedEngine over launch.mesh.make_client_mesh):
    # 0 = single-device engine; N = shard the (M, ...) client stacks over a
    # 1-D "clients" axis of min(N, available devices)
    clients: int = 0

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pods, self.data, self.model) if self.multi_pod else (self.data, self.model)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def num_devices(self) -> int:
        n = self.data * self.model
        return n * self.pods if self.multi_pod else n


@dataclass(frozen=True)
class InputShape:
    name: str = "train_4k"
    seq_len: int = 4096
    global_batch: int = 256
    kind: str = "train"             # train | prefill | decode


# The four assigned input shapes (brief).
INPUT_SHAPES = {
    "train_4k":    InputShape("train_4k",    4096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768,  32,  "prefill"),
    "decode_32k":  InputShape("decode_32k",  32768,  128, "decode"),
    "long_500k":   InputShape("long_500k",   524288, 1,   "decode"),
}


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: str = "cosine"        # constant | linear | cosine
    grad_accum: int = 1
    seed: int = 0


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    dp: DPConfig = field(default_factory=DPConfig)
    p4: P4Config = field(default_factory=P4Config)
    kernels: KernelConfig = field(default_factory=KernelConfig)
    schedule: ScheduleConfig = field(default_factory=ScheduleConfig)
    topology: TopologyConfig = field(default_factory=TopologyConfig)
    faults: FaultConfig = field(default_factory=FaultConfig)


# ---------------------------------------------------------------------------
# (De)serialization + CLI overrides
# ---------------------------------------------------------------------------

def to_dict(cfg: Any) -> Any:
    if dataclasses.is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in dataclasses.fields(cfg)}
    if isinstance(cfg, (list, tuple)):
        return [to_dict(x) for x in cfg]
    return cfg


def to_json(cfg: Any) -> str:
    return json.dumps(to_dict(cfg), indent=2)


def apply_overrides(cfg, overrides: dict):
    """Apply dotted-path overrides, e.g. {"dp.epsilon": 3.0, "model.window": 8192}."""
    for path, value in overrides.items():
        parts = path.split(".")
        cfg = _set_path(cfg, parts, value)
    return cfg


def _set_path(cfg, parts, value):
    if len(parts) == 1:
        f = {f.name: f for f in dataclasses.fields(cfg)}[parts[0]]
        typ = f.type if isinstance(f.type, type) else None
        cur = getattr(cfg, parts[0])
        if isinstance(cur, bool):
            value = value in (True, "true", "True", "1", 1)
        elif isinstance(cur, int) and not isinstance(value, bool):
            value = int(value)
        elif isinstance(cur, float):
            value = float(value)
        return dataclasses.replace(cfg, **{parts[0]: value})
    child = getattr(cfg, parts[0])
    return dataclasses.replace(cfg, **{parts[0]: _set_path(child, parts[1:], value)})
