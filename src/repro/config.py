"""Configuration system for the HeteroRL/GEPO framework.

Everything is a frozen dataclass so configs are hashable and usable as jit
static arguments. `ModelConfig` describes any of the supported architecture
families via a per-layer *block pattern* that is cycled over the depth; the
model code scans over homogeneous super-blocks of one pattern period.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

# Layer kinds usable in ``block_pattern``.
ATTN = "attn"          # global causal self-attention
LOCAL = "local"        # sliding-window causal self-attention
MAMBA = "mamba"        # Mamba2 / SSD block (attention-free)
CROSS = "cross"        # cross-attention to a stub modality memory (VLM)

# FFN kinds usable in ``ffn_pattern``.
MLP = "mlp"
MOE = "moe"
NONE = "none"          # e.g. Mamba2 blocks carry no separate FFN


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // num_heads

    # layer layout -------------------------------------------------------
    block_pattern: Tuple[str, ...] = (ATTN,)
    ffn_pattern: Tuple[str, ...] = (MLP,)

    # attention options ---------------------------------------------------
    qkv_bias: bool = False
    sliding_window: int = 4096      # used by LOCAL layers
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 1_000_000.0

    # MoE options ---------------------------------------------------------
    num_experts: int = 0            # the router's width
    experts_per_token: int = 0
    shared_expert: bool = False
    # The experts this layer holds, (first expert id, how many): one
    # chip's block of an expert-parallel deployment, which computes only
    # its own experts' part of the result. None holds all of them.
    expert_block: Optional[Tuple[int, int]] = None

    # SSM (Mamba2 / SSD) options -----------------------------------------
    ssm_state: int = 0              # N, state dimension
    ssm_headdim: int = 64           # P, channels per SSM head
    ssm_expand: int = 2             # d_inner = expand * d_model
    ssm_ngroups: int = 1            # B/C groups
    ssm_conv: int = 4               # depthwise conv width
    ssm_chunk: int = 256            # SSD chunk length

    # encoder / multimodal stubs -----------------------------------------
    encoder_layers: int = 0         # >0 -> encoder-decoder (whisper)
    encoder_seq: int = 0            # frames for audio / patches for vision
    memory_seq: int = 0             # stub modality memory length for CROSS

    # numerics ------------------------------------------------------------
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    scale_embed: bool = False       # multiply embeddings by sqrt(d) (gemma)

    # implementation knobs (not architecture) -----------------------------
    attn_impl: str = "chunked"      # naive | chunked | pallas
    attn_chunk: int = 512           # query/kv block for chunked attention
    # Paged-attention backend for the continuous engine
    # (repro.kernels.ops.paged_decode / paged_prefill): "auto" decodes
    # with the in-place Pallas kernel on a TPU and gathers the logical KV
    # view elsewhere (bit-identical to the dense decode path: the static
    # ≡ continuous parity contract), and prefills through the gather
    # view everywhere; an engine on a multi-device plan resolves it to
    # "gather" (a pallas_call has no GSPMD rule). "gather" | "ref" |
    # "pallas" force a backend.
    paged_attn_impl: str = "auto"
    remat: bool = True              # activation checkpointing per block
    # residual-stream sharding constraint between blocks (set by the
    # launcher; nested tuples of mesh axis names / None). E.g. Megatron-SP
    # style ((("pod","data"),), "model", None) shards (B, S, d) as
    # batch->dp, seq->model.
    act_sharding: Optional[Tuple] = None
    # §Perf H-A1 (REFUTED for dense-train: 3.3× more collective bytes —
    # see EXPERIMENTS.md): force head-sharded full-S q/k/v before attention.
    attn_gather_qkv: bool = False
    # §Perf H-B2/H-C3: shard_map expert-parallel MoE ("train"|"serve",
    # None = GSPMD baseline); ep_dp_axes = data axes of the mesh.
    moe_ep: Optional[str] = None
    ep_dp_axes: Optional[Tuple[str, ...]] = None
    # §Perf H-G1: ring-buffer KV cache for LOCAL (sliding-window) layers —
    # the cache stores only `sliding_window` entries (gemma2 long-context
    # decode: local-layer KV shrinks seq_len/window ≈ 128×).
    local_ring_kv: bool = False

    # ---------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        period = len(self.block_pattern)
        assert self.num_layers % period == 0, (
            f"{self.name}: num_layers={self.num_layers} not divisible by "
            f"pattern period {period}")
        if self.expert_block is not None:
            first, count = map(int, self.expert_block)   # JSON gives a list
            if not (count >= 1 and 0 <= first
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"{self.name}: expert_block {self.expert_block} is not "
                    f"within the {self.num_experts} experts")
            object.__setattr__(self, "expert_block", (first, count))

    # derived -------------------------------------------------------------
    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_blocks(self) -> int:
        """Number of scanned super-blocks (one pattern period each)."""
        return self.num_layers // self.period

    @property
    def padded_vocab(self) -> int:
        """Vocab padded so it shards evenly over 16-way model parallelism
        and stays lane-aligned (multiples of 256)."""
        return _round_up(self.vocab_size, 256)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    @property
    def first_held_expert(self) -> int:
        return self.expert_block[0] if self.expert_block else 0

    @property
    def num_held_experts(self) -> int:
        return self.expert_block[1] if self.expert_block else self.num_experts

    def ffn_kind(self, layer_in_block: int) -> str:
        return self.ffn_pattern[layer_in_block % len(self.ffn_pattern)]

    @property
    def has_attention(self) -> bool:
        return any(k in (ATTN, LOCAL, CROSS) for k in self.block_pattern)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    # parameter counting (for roofline MODEL_FLOPS = 6·N·D) ---------------
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count; ``active_only`` counts only the
        experts that fire per token (for MoE rooflines)."""
        d, h = self.d_model, self.head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        total = self.padded_vocab * d          # embedding
        if not self.tie_embeddings:
            total += self.padded_vocab * d     # lm head
        for li in range(self.num_layers):
            kind = self.block_pattern[li % self.period]
            if kind in (ATTN, LOCAL, CROSS):
                total += d * (n_q * h) + 2 * d * (n_kv * h) + (n_q * h) * d
            elif kind == MAMBA:
                di, N, G = self.d_inner, self.ssm_state, self.ssm_ngroups
                total += d * (2 * di + 2 * G * N + self.ssm_heads)  # in_proj
                total += di * d                                      # out_proj
                total += self.ssm_conv * (di + 2 * G * N)            # conv
            fk = self.ffn_kind(li % self.period)
            if fk == MLP:
                total += 3 * d * self.d_ff
            elif fk == MOE:
                n_e = (self.experts_per_token if active_only
                       else self.num_held_experts)
                total += 3 * d * self.d_ff * n_e
                total += d * self.num_experts                        # router
                if self.shared_expert:
                    total += 3 * d * self.d_ff
            total += 2 * d                                            # norms
        if self.encoder_layers:
            total += self.encoder_layers * (4 * d * (self.num_heads * h)
                                            + 3 * d * self.d_ff + 2 * d)
        return total


@dataclass(frozen=True)
class ShapeConfig:
    """One of the assigned input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")
INPUT_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}


@dataclass(frozen=True)
class RLConfig:
    """Policy-optimization settings (paper §3/§4 + App. B)."""
    loss_type: str = "gepo"        # grpo|dr_grpo|bnpo|gspo|gepo|tis|cispo|topr
    group_size: int = 8
    clip_eps: float = 0.2          # PPO-style clip (token/seq level methods)
    cispo_eps_low: float = 1.0     # IW clip band for CISPO
    cispo_eps_high: float = 0.27
    beta_kl: float = 0.005         # CPPO-KL coefficient (0 => off)
    adv_normalize: bool = True     # divide by group std (off for dr_grpo)
    seq_len_normalize: bool = True # length-norm of seq logprob (GSPO eq. 61)
    gepo_smooth: float = 0.0       # App. H defensive denominator: λ·p mix
    temperature: float = 0.6
    top_k: int = 20
    top_p: float = 0.95
    max_new_tokens: int = 32
    recompute_sampler_logps: bool = True   # App. B.1 vLLM/FSDP mismatch fix
    entropy_bonus: float = 0.0
    # Generation engine for sampler nodes: "static" = one lax.scan to
    # max_new_tokens; "continuous" = slot pool + paged KV cache with EOS
    # slot recycling (see repro/sampling/scheduler.py).
    engine: str = "static"


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-6
    warmup_frac: float = 0.03
    total_steps: int = 1000
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    grad_accum: int = 1
    seed: int = 0
    # Learner device mesh as "DxM" (data×model; "PxDxM" adds the slow
    # inter-pod axis). "1x1" = single device. Resolved by the unified
    # execution layer (repro.parallel.plan_from_flag); on CPU a >1 mesh
    # needs XLA_FLAGS=--xla_force_host_platform_device_count=N exported
    # before the first jax import.
    mesh: str = "1x1"
    # Learner-side log-prob implementation (the RL hot path):
    #   "fused"   — auto-dispatch repro.kernels.ops.fused_token_logprob
    #               (Pallas TPU kernel, chunked lax.map elsewhere); no
    #               V-sized f32 activation in forward or backward.
    #   "pallas" | "chunked" — force one fused backend.
    #   "naive"   — materializing log-softmax (repro.core.logprob).
    logprob_impl: str = "fused"


@dataclass(frozen=True)
class HeteroConfig:
    """HeteroRL runtime settings (paper §4.1 + App. E)."""
    num_samplers: int = 4
    max_delay_steps: int = 64        # staleness window in learner steps
    delay_distribution: str = "lognormal"   # lognormal | weibull | exponential
    delay_min_s: float = 60.0
    delay_max_s: float = 1800.0
    delay_median_s: float = 60.0
    sync_interval_steps: int = 1     # learner checkpoint publish period
    window_s: float = 1800.0         # rollout eligibility window
    seed: int = 0
    # Simulated WAN bandwidth of the model-sync link (Mbit/s). The default
    # inf reproduces the legacy payload-blind delay model bit-for-bit; a
    # finite value adds payload_bytes/bandwidth serialization time on top
    # of the sampled propagation delay (latency.sync_delay_s), so D_M
    # finally depends on how many bytes the transport actually moves.
    bandwidth_mbps: float = float("inf")
    # Sampler-node device mesh as "DxM" (serve-mode tensor parallelism);
    # same conventions as TrainConfig.mesh. All sampler nodes share it —
    # HeteroRL's point is that it can differ from the learner's mesh.
    sampler_mesh: str = "1x1"
    # Sampler-side paged-decode backend override (ModelConfig.
    # paged_attn_impl vocabulary; None keeps the arch default). Lets the
    # hetero sweeps A/B the in-place kernel against the gather path.
    paged_attn_impl: Optional[str] = None
    # Sampler-side speculative decoding (continuous engine only): draft
    # cap per verification round; 0 = off. Distribution-preserving, so
    # table2-style runs can A/B it purely as a decode-latency lever.
    spec_k: int = 0


@dataclass(frozen=True)
class ServeConfig:
    """Deployment settings for a generation engine and its serving front
    door — one shared config object instead of the nine loose argparse
    flags ``launch/serve.py`` used to carry.

    Split of responsibilities: :class:`repro.serving.api.SamplingParams`
    describes a *request* (temperature/top-k/top-p/token budget);
    ``ServeConfig`` describes a *deployment* (engine kind, KV capacity,
    decode horizon, mesh, admission limits). The same object configures
    batch serving (``launch/serve.py``), the asyncio front door
    (``repro.serving.server``), and HeteroRL sampler nodes.
    """
    # engine ---------------------------------------------------------------
    engine: str = "continuous"       # static | continuous
    num_slots: int = 8               # decode slots (continuous engine)
    page_size: int = 16              # KV page size in tokens
    prefill_chunk: int = 0           # prompt tokens per chunk (0 = whole)
    sync_every: int = 8              # decode horizon per scheduler sync
    # capacity: per-request prompt+completion cap; the page pool defaults
    # to 1 scratch + num_slots * pages_for(max_total_tokens) pages, and
    # num_pages overrides it (smaller = real admission pressure, larger =
    # headroom for the shared-prefix cache to keep pages resident)
    max_total_tokens: int = 256
    num_pages: int = 0               # 0 = derive from slots × budget
    prefix_cache: bool = True        # shared-prefix KV page reuse
    prefix_cache_entries: int = 64
    mesh: str = "1x1"                # serve mesh DxM (TrainConfig.mesh conv.)
    paged_attn_impl: Optional[str] = None   # ModelConfig override (None=keep)
    # speculative decoding (continuous engine only): drafts per
    # verification round (0 = off). Acceptance preserves the sampled
    # distribution exactly; greedy stays bit-identical to spec off.
    spec_k: int = 0
    spec_ngram_max: int = 3          # prompt-lookup suffix n-gram (longest)
    spec_ngram_min: int = 1          # ... shortest suffix tried
    # rescore acceptance through one fused paged_prefill_layers launch
    # per round and export max |fused - in-forward| as a drift gauge
    spec_rescore: bool = True
    # front door -----------------------------------------------------------
    host: str = "127.0.0.1"
    port: int = 8100
    max_queue: int = 256             # admission: queued-request cap
    # admission: shed load once the KV pages promised to queued requests
    # exceed this many turns of the page pool (1.0 = the queue may never
    # hold more demand than the pool serves in one full drain)
    queue_overcommit: float = 4.0
    default_priority: int = 1        # priority class for unlabelled requests
    default_deadline_s: float = 0.0  # TTFT SLO applied when none given (0=off)
    seed: int = 0

    def __post_init__(self):
        if self.engine not in ("static", "continuous"):
            raise ValueError(f"engine={self.engine!r} not static|continuous")
        if self.num_slots < 1 or self.page_size < 1 or self.sync_every < 1:
            raise ValueError("num_slots, page_size, sync_every must be >= 1")
        if self.max_total_tokens < 2:
            raise ValueError("max_total_tokens must hold a prompt token "
                             "and a completion token at least")
        if self.prefill_chunk < 0 or self.num_pages < 0:
            raise ValueError("prefill_chunk / num_pages must be >= 0")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if self.queue_overcommit < 1.0:
            raise ValueError("queue_overcommit < 1 would reject requests "
                             "an idle pool could serve")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 = speculation off)")
        if self.spec_k > 0 and self.engine != "continuous":
            raise ValueError("speculative decoding (spec_k > 0) needs the "
                             "continuous engine")
        if not 1 <= self.spec_ngram_min <= self.spec_ngram_max:
            raise ValueError("need 1 <= spec_ngram_min <= spec_ngram_max")

    # derived --------------------------------------------------------------
    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_total_tokens // self.page_size)

    @property
    def resolved_num_pages(self) -> int:
        """Page-pool size: explicit ``num_pages``, or scratch + the full
        budget for every slot — plus 50% headroom when the prefix cache
        is on, so cached prefixes survive full slot occupancy instead of
        being evicted the moment every slot reserves its worst-case
        budget."""
        if self.num_pages:
            return self.num_pages
        base = self.num_slots * self.pages_per_slot
        headroom = base // 2 if self.prefix_cache else 0
        return 1 + base + headroom


def smoke_variant(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A reduced same-family variant for CPU smoke tests: ≤2 pattern periods
    of layers, d_model ≤ 256, ≤ 4 experts."""
    period = cfg.period
    small = dict(
        num_layers=2 * period if 2 * period <= 4 else period,
        d_model=256 if cfg.d_model >= 256 else cfg.d_model,
        num_heads=min(cfg.num_heads, 4) if cfg.num_heads else 0,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 0,
        head_dim=64 if cfg.num_heads else 0,
        d_ff=512 if cfg.d_ff else 0,
        vocab_size=512,
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_seq=32 if cfg.encoder_seq else 0,
        memory_seq=16 if cfg.memory_seq else 0,
        ssm_state=min(cfg.ssm_state, 32) if cfg.ssm_state else 0,
        ssm_headdim=32 if cfg.ssm_state else cfg.ssm_headdim,
        ssm_chunk=16 if cfg.ssm_state else cfg.ssm_chunk,
        sliding_window=min(cfg.sliding_window, 64),
        attn_impl="naive",
        attn_chunk=32,
        dtype="float32",
        remat=False,
        name=cfg.name + "-smoke",
    )
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
