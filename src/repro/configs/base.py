"""Config dataclasses + registry for architectures, input shapes, and federation.

Every assigned architecture gets one module ``src/repro/configs/<id>.py`` (dashes
mapped to underscores) exporting ``CONFIG: ArchConfig``. The registry below resolves
``--arch <id>`` strings.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # if >0, a shared (always-on) dense ffn of this width runs alongside experts
    d_ff_shared: int = 0
    # expert parallelism (hybrid_moe): the router scores ``n_routed`` experts
    # (0 = n_experts) and this chip holds ``n_experts`` of them, ids
    # first_expert .. first_expert + n_experts - 1
    n_routed: int = 0
    first_expert: int = 0

    @property
    def router_width(self) -> int:
        return self.n_routed or self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int
    expand: int = 2            # d_inner = expand * d_model
    conv_width: int = 4
    # mamba2 multi-head state layout
    head_dim: int = 64
    version: int = 1           # 1 = mamba1 (falcon-mamba), 2 = mamba2 (zamba2)


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (whisper) architectures."""
    n_layers: int
    # frontends (conv/mel, ViT) are stubbed: input_specs provides embeddings.


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                # dense | moe | ssm | hybrid | hybrid_moe | encdec | vlm
    source: str                # citation from the assignment
    n_layers: int
    d_model: int
    n_heads: int               # 0 for attention-free
    n_kv_heads: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    head_dim: int = 0          # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    # hybrid: apply the shared attention block every `shared_attn_every` layers
    shared_attn_every: int = 0
    # hybrid_moe: each layer's mixer, "mamba" or "attention", in order; every
    # layer has its own weights and an MoE block
    layer_types: Tuple[str, ...] = ()
    # granite-style multipliers: embeddings scaled up, residual branches
    # scaled down, logits divided; attention_multiplier 0 -> head_dim**-0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0
    use_rope: bool = True      # False: no positional encoding (NoPE)
    tie_embeddings: bool = False
    # sliding-window attention width used for the long_500k serve variant; dense
    # archs fall back to this window there (see DESIGN.md §5).
    long_context_window: int = 4096
    # multimodal early-fusion stub: number of prefix positions replaced by
    # precomputed patch/frame embeddings ([vlm]/[audio]/llama4 early fusion).
    n_prefix_embeds: int = 0
    # federated placement: "replica" (M = pods*data clients, full per-client copy)
    # or "zero" (M = pods clients, state FSDP over data axis).
    fed_mode: str = "replica"
    dtype: str = "bfloat16"

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        assert self.n_heads > 0
        return self.d_model // self.n_heads

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def attn_scale(self) -> float:
        return self.attention_multiplier or self.resolved_head_dim ** -0.5

    def n_of(self, kind: str) -> int:
        """Layers of the pattern whose mixer is ``kind``."""
        return sum(t == kind for t in self.layer_types)

    def param_count(self) -> int:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        d = self.d_model
        n = 0
        n += self.vocab * d            # embed
        n += d if self.tie_embeddings else self.vocab * d + d  # head + norm
        attn = mlp = ssm = 0
        if self.n_heads:
            hd = self.resolved_head_dim
            attn = (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                    + self.n_heads * hd * d + 2 * d)
            if self.qkv_bias:
                attn += (self.n_heads + 2 * self.n_kv_heads) * hd
        if self.moe is not None:
            e = self.moe
            mlp = d * e.router_width + e.n_experts * 3 * d * e.d_ff_expert
            if e.d_ff_shared:
                mlp += 3 * d * e.d_ff_shared
        elif self.d_ff:
            mlp = 3 * d * self.d_ff
        if self.ssm is not None:
            s = self.ssm
            di = s.expand * d
            # in_proj (x,z), conv, dt/B/C projections, out_proj (approx)
            ssm = (2 * d * di + s.conv_width * di
                   + di * s.state_dim * 2 + di + di * d)
        if self.family == "hybrid_moe":
            n += (ssm * self.n_of("mamba") + attn * self.n_of("attention")
                  + mlp * self.n_layers)
        elif self.shared_attn_every:
            # hybrid (zamba2-style): SSM per layer + ONE weight-tied attn+mlp block
            n += ssm * self.n_layers + attn + mlp
        elif self.family == "ssm":
            n += ssm * self.n_layers
        else:
            n += (attn + mlp) * self.n_layers
        if self.encoder is not None:
            n += (attn + mlp) * self.encoder.n_layers
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE discounts inactive experts)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        inactive = (e.n_experts - e.top_k) * 3 * self.d_model * e.d_ff_expert
        return self.param_count() - inactive * self.n_layers


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


CODECS = ("none", "int8", "topk")

TOPOLOGIES = ("ring", "torus2d", "complete", "erdos")


def validate_topology(name: str, er_p: float, time_varying: bool) -> None:
    """Shared gossip-topology validation — ``PopulationConfig`` and
    ``repro.fed.topology.GossipAggregator`` both call this, so the two
    construction paths can never drift apart. Raises ``ValueError``."""
    if name not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                         f"got {name!r}")
    if not 0.0 <= er_p <= 1.0:
        raise ValueError(f"er_p must be in [0, 1], got {er_p}")
    if time_varying and name != "erdos":
        raise ValueError("time_varying resamples an Erdős–Rényi graph every "
                         "round: the fixed topologies (ring/torus2d/"
                         "complete) are static by definition — set "
                         "topology='erdos'")


def validate_codec(name: str, bits: int, topk_frac: float) -> None:
    """Shared codec validation — ``FedConfig`` and
    ``repro.fed.compress.make_codec`` both call this, so the two
    construction paths can never drift apart. Raises ``ValueError``."""
    if name not in CODECS:
        raise ValueError(f"codec must be one of {CODECS}, got {name!r}")
    if not 2 <= bits <= 8:
        raise ValueError(f"codec_bits must be in [2, 8] (levels are shipped "
                         f"bit-packed, one f32 scale per tensor), "
                         f"got {bits}")
    if not 0.0 < topk_frac <= 1.0:
        raise ValueError(f"topk_frac must be in (0, 1] (1 = keep every "
                         f"entry), got {topk_frac}")


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """AdaFBiO hyper-parameters (Algorithm 1)."""
    q: int = 8                  # local steps between syncs
    neumann_k: int = 8          # K in Eq. (15)
    lr_x: float = 1e-3          # gamma
    lr_y: float = 1e-2          # lambda
    eta: float = 0.5            # eta_t (momentum interpolation); schedule in core
    alpha_c1: float = 4.0       # alpha_{t+1} = c1 * eta_t^2
    beta_c2: float = 4.0        # beta_{t+1}  = c2 * eta_t^2
    rho: float = 1e-4           # adaptive-matrix regularizer
    varrho: float = 0.9         # EMA for adaptive matrices
    nu: float = 1e-3            # LL strong-convexity regularizer
    theta: float = 1.0          # Neumann step (vartheta in paper, <= 1/L_g)
    adaptive: str = "adam"      # adam | adabelief | none
    eta_k: float = 1.0          # k in eta_t = k M^{1/3} / (n+t)^{1/3}
    eta_n: float = 64.0         # n in the eta_t schedule
    # UL (f) batch and Neumann batch sizes as fractions of the LL batch
    ul_batch_frac: float = 0.125
    neumann_batch: int = 1
    # gradient-accumulation bound: sequences per microbatch per data shard
    microbatch_per_shard: int = 1
    # fused flat-buffer update path (STORM refresh + Eq. 14) — "auto" uses the
    # Pallas kernels on one TPU device and the per-leaf jnp path elsewhere,
    # including meshes of several devices; "on" forces the flat-buffer path
    # (jnp reference math off-TPU); "off" disables it (core.adafbio.use_fused)
    fused: str = "auto"
    # ---- communication compression (repro.fed.compress) ----
    # client→server update codec: "none" (full-precision, bit-identical to
    # the uncompressed path), "int8" (stochastic uniform quantization to
    # codec_bits-bit levels, Pallas-fused on TPU), "topk" (magnitude
    # sparsification keeping a topk_frac fraction of each tensor)
    codec: str = "none"
    # int8 codec: quantization bit width b; levels span [-(2^(b-1)-1),
    # 2^(b-1)-1], shipped bit-packed with one f32 scale per tensor
    codec_bits: int = 8
    # topk codec: fraction of each tensor's entries transmitted (1.0 keeps
    # everything — matches codec="none" up to float rounding)
    topk_frac: float = 0.1
    # error feedback: keep the per-client compression residual and fold it
    # into the next transmission (EF-SGD; lossy codecs only)
    error_feedback: bool = True

    def __post_init__(self):
        validate_codec(self.codec, self.codec_bits, self.topk_frac)


DELAY_MODELS = ("uniform", "tiers", "lognormal", "trace")


def validate_delay_model(name: str, max_delay: int, tier_fracs, tier_delays,
                         delay_sigma: float) -> None:
    """Shared delay-model validation — ``PopulationConfig`` and
    ``repro.fed.population.make_delay_model`` both call this, so the two
    construction paths can never drift apart. Raises ``ValueError``."""
    if name not in DELAY_MODELS:
        raise ValueError(f"delay_model must be one of {DELAY_MODELS}, "
                         f"got {name!r}")
    if max_delay < 1:
        raise ValueError(f"max_delay must be >= 1 round, got {max_delay}")
    if name == "tiers":
        if len(tier_fracs) != len(tier_delays) or not tier_fracs:
            raise ValueError(
                f"tiers need matching non-empty tier_fracs/tier_delays, "
                f"got {len(tier_fracs)} fracs, {len(tier_delays)} delay "
                f"ranges")
        if (any(f <= 0 for f in tier_fracs)
                or abs(sum(tier_fracs) - 1.0) > 1e-6):
            raise ValueError(f"tier_fracs must be positive and sum to 1, "
                             f"got {tier_fracs}")
        if any(not 1 <= lo <= hi for lo, hi in tier_delays):
            raise ValueError(f"each tier delay range needs 1 <= lo <= hi "
                             f"rounds, got {tier_delays}")
    if name == "lognormal":
        if delay_sigma < 0:
            raise ValueError(f"delay_sigma must be >= 0, got {delay_sigma}")
        if max_delay < 2:
            raise ValueError(
                "lognormal delays are clipped to [1, max_delay]: "
                "max_delay=1 makes every delay 1 (the degenerate "
                "no-heterogeneity case) — set max_delay >= 2")


@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    """Client population ≫ per-round cohort (repro.fed.population).

    ``n`` persistent client states live in a bank; each round a sampler
    picks a ``cohort`` of C clients, and only those C are computed (gather →
    fused scan round → scatter), so per-round compute is O(C) not O(n).
    """
    n: int                          # population size N
    cohort: int                     # per-round compute cohort C
    sampler: str = "uniform"        # uniform | roundrobin | trace | trace-file
    sync_mode: str = "broadcast"    # broadcast | participants (fed.population)
    # staleness-aware aggregation: weight ∝ (1 + rounds_since_sync)^-decay;
    # 0 = plain uniform cohort average (only meaningful with participants sync)
    staleness_decay: float = 0.0
    # availability-trace sampler schedule (sampler == "trace")
    trace_period: int = 8
    trace_duty: float = 0.5
    # recorded-trace replay (sampler == "trace-file"): JSONL of per-client
    # up intervals, see docs/async.md for the format spec
    trace_file: Optional[str] = None
    # ---- asynchronous execution (fed.population.make_async_round) ----
    # 0 = synchronous rounds (today's path, bit-identical); > 0 enables
    # async execution and drops arriving updates staler than this many
    # rounds (float("inf") = async with no gating)
    max_staleness: float = 0.0
    # per-dispatch return delay is uniform over [1, max_delay] rounds;
    # > 1 makes cohorts genuinely overlap (a client can be sampled while
    # still in flight)
    max_delay: int = 1
    # delay-adaptive server step à la Jiao et al. (arXiv:2212.10048):
    # the model movement scales by 1 / (1 + delay_eta * (mean_tau - 1));
    # 0 disables
    delay_eta: float = 0.0
    # ---- heterogeneous per-client delay model (fed.population.DelayModel):
    #   uniform   — delay ~ U[1, max_delay] per dispatch (the default;
    #               bit-identical to the plain async path)
    #   tiers     — each client permanently assigned to a speed tier
    #               (tier_fracs) with per-tier delay ranges (tier_delays)
    #   lognormal — permanent per-client latency exp(delay_mu +
    #               delay_sigma * z_i) quantized to rounds, clipped to
    #               [1, max_delay]
    #   trace     — per-round delays replayed from trace_file's optional
    #               per-client "delay" field (docs/async.md)
    delay_model: str = "uniform"
    # tiers model: population fraction per tier (largest-remainder split)
    # and the [lo, hi] per-dispatch delay range of each tier, default
    # 20/60/20 fast/medium/straggler
    tier_fracs: Tuple[float, ...] = (0.2, 0.6, 0.2)
    tier_delays: Tuple[Tuple[int, int], ...] = ((1, 1), (2, 4), (4, 8))
    # lognormal model: log-latency location/scale (in rounds)
    delay_mu: float = 0.0
    delay_sigma: float = 0.5
    # ---- gossip engine (repro.fed.topology) ----
    # mixing topology of the decentralized rounds; the star engines ignore
    # these. "erdos" draws a seeded static graph (ring backbone unioned in
    # for connectivity) unless time_varying resamples it every round.
    topology: str = "ring"
    er_p: float = 0.4               # Erdős–Rényi edge probability
    time_varying: bool = False      # resample the graph each round (erdos)
    topology_seed: int = 0

    def __post_init__(self):
        if not 1 <= self.cohort <= self.n:
            raise ValueError(f"need 1 <= cohort <= n, got cohort="
                             f"{self.cohort}, n={self.n}")
        if self.sync_mode not in ("broadcast", "participants"):
            raise ValueError(f"sync_mode must be 'broadcast' or "
                             f"'participants', got {self.sync_mode!r}")
        if self.sampler not in ("uniform", "roundrobin", "trace",
                                "trace-file"):
            raise ValueError(f"sampler must be one of uniform/roundrobin/"
                             f"trace/trace-file, got {self.sampler!r}")
        if self.sampler == "trace-file" and not self.trace_file:
            raise ValueError("sampler='trace-file' needs trace_file=<path>")
        if self.max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0 (0 = synchronous),"
                             f" got {self.max_staleness}")
        if self.max_delay < 1:
            raise ValueError(f"max_delay must be >= 1 round, "
                             f"got {self.max_delay}")
        if self.delay_eta < 0:
            raise ValueError(f"delay_eta must be >= 0, got {self.delay_eta}")
        validate_delay_model(self.delay_model, self.max_delay,
                             self.tier_fracs, self.tier_delays,
                             self.delay_sigma)
        validate_topology(self.topology, self.er_p, self.time_varying)
        if self.delay_model == "trace" and not self.trace_file:
            raise ValueError("delay_model='trace' replays the trace_file's "
                             "per-client 'delay' field: set "
                             "trace_file=<path> (format: docs/async.md)")
        if self.max_staleness == 0 and (self.max_delay > 1
                                        or self.delay_eta > 0
                                        or self.delay_model != "uniform"):
            raise ValueError("max_delay > 1 / delay_eta > 0 / a non-uniform"
                             " delay_model are async knobs: set "
                             "max_staleness > 0 (or float('inf')) to "
                             "enable asynchronous execution")

    @property
    def asynchronous(self) -> bool:
        """True when rounds run the async path (overlapping cohorts,
        delayed arrivals, bounded-staleness gating)."""
        return self.max_staleness != 0


_ARCH_IDS = [
    "whisper-tiny",
    "zamba2-1.2b",
    "qwen2.5-14b",
    "internvl2-76b",
    "qwen3-moe-30b-a3b",
    "falcon-mamba-7b",
    "deepseek-67b",
    "granite-20b",
    "llama4-scout-17b-a16e",
    "qwen1.5-4b",
    "granite-4.0-h-small",
]


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "p")


def list_arch_ids() -> Tuple[str, ...]:
    return tuple(_ARCH_IDS)


def get_arch(arch_id: str) -> ArchConfig:
    if arch_id not in _ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {_ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.CONFIG


def get_shape(shape_id: str) -> ShapeConfig:
    return INPUT_SHAPES[shape_id]


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """A smoke-test-sized variant of the same family (<=2 layers, d_model<=512)."""
    d = min(cfg.d_model, 256)
    heads = min(cfg.n_heads, 4) if cfg.n_heads else 0
    kv = min(cfg.n_kv_heads, heads) if heads else 0
    if heads and cfg.n_kv_heads == cfg.n_heads:
        kv = heads                           # keep MHA archs MHA
    if heads and cfg.n_kv_heads == 1:
        kv = 1                               # keep MQA archs MQA
    changes = dict(
        n_layers=2,
        d_model=d,
        n_heads=heads,
        n_kv_heads=kv,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab=min(cfg.vocab, 512),
        head_dim=(d // heads if heads else 0),
    )
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=min(cfg.moe.top_k, 2),
            n_routed=min(cfg.moe.n_routed, 8),
            d_ff_expert=min(cfg.moe.d_ff_expert, 128),
            d_ff_shared=min(cfg.moe.d_ff_shared, 128) if cfg.moe.d_ff_shared else 0)
    if cfg.ssm is not None:
        changes["ssm"] = dataclasses.replace(
            cfg.ssm, state_dim=min(cfg.ssm.state_dim, 16),
            head_dim=min(cfg.ssm.head_dim, 32))
    if cfg.encoder is not None:
        changes["encoder"] = EncoderConfig(n_layers=2)
    if cfg.shared_attn_every:
        changes["shared_attn_every"] = 2
    if cfg.layer_types:
        changes["layer_types"] = ("mamba", "attention")
    if cfg.n_prefix_embeds:
        changes["n_prefix_embeds"] = 8
    changes.update(overrides)
    return dataclasses.replace(cfg, **changes)
