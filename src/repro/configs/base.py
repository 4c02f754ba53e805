"""Model / run configuration system.

``ModelConfig`` is a frozen dataclass describing any of the six architecture
families; ``layer_kinds`` derives the per-layer (mixer, ffn) pattern used by
the period-block scan in :mod:`repro.models.transformer`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

__all__ = ["ModelConfig", "RunConfig", "layer_kinds", "block_period",
           "period_kinds", "n_blocks", "reduced"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 32000
    head_dim: int = 0                # 0 → d_model // n_heads
    # attention options
    pos_emb: str = "rope"            # rope | sinusoidal (encdec)
    rope_theta: float = 1e4
    # multi-head latent attention (DeepSeek-V2): attn_kind="mla" projects
    # keys and values through a normed kv_lora_rank latent; q/k heads are
    # qk_nope_head_dim + qk_rope_head_dim wide, v heads v_head_dim, and one
    # rope key of qk_rope_head_dim is shared by every head
    attn_kind: str = "gqa"           # gqa | mla
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (rope_factor > 1): frequencies between the beta_fast
    # and beta_slow rotation counts of the original context are ramped from
    # θ^(-2i/d) down to θ^(-2i/d)/factor; softmax scale × mscale² where
    # mscale = 0.1·yarn_mscale_all_dim·ln(factor) + 1 (cos/sin unscaled)
    rope_factor: float = 1.0
    rope_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    qk_norm: bool = False
    qkv_bias: bool = False
    mlp_gated: bool = True           # SwiGLU vs plain GELU MLP
    sliding_window: int = 0          # 0 = full causal attention
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_every: int = 1               # layer i uses MoE FFN iff i % moe_every == moe_offset
    moe_offset: int = 0
    dense_d_ff: int = 0              # ffn width of non-MoE layers in mixed models
    first_k_dense: int = 0           # leading dense layers, before the period scan
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # expert share (DESIGN §14): the layer holds the n_experts_held experts
    # with global ids [expert_first, expert_first + n_experts_held) of the
    # router's n_experts (0 = all), and routes over all of them; dropless
    # routing computes every assignment to a held expert (no capacity)
    n_experts_held: int = 0
    expert_first: int = 0
    moe_dropless: bool = False
    norm_topk_prob: bool = True      # renormalise the top-k gate weights
    routed_scaling_factor: float = 1.0
    router_aux: str = "switch"       # switch (batch-wise) | seq (sequence-wise)
    # SSM (Mamba-1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0             # 0 → ceil(d_model / 16)
    # hybrid: layer i is attention iff i % attn_every == attn_offset (else SSM)
    attn_every: int = 0              # 0 → all attention (or all-SSM for family=ssm)
    attn_offset: int = 0
    # encoder-decoder (audio)
    n_enc_layers: int = 0
    # modality frontend stub: number of precomputed embedding tokens supplied
    n_frontend_tokens: int = 0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # citation / provenance (model card or paper)
    source: str = ""

    # ---- derived ---------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def qk_head_dim(self) -> int:
        """Width of a query/key head (MLA: nope + rope parts)."""
        if self.attn_kind == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.hd

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or math.ceil(self.d_model / 16)

    @property
    def is_decoder_lm(self) -> bool:
        return self.family in ("dense", "moe", "ssm", "hybrid", "vlm")

    def n_params(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, V = self.d_model, self.vocab_size
        total = V * d * 2 + d  # embed + untied lm head + final norm
        for mixer, ffn in layer_kinds(self):
            if mixer == "attn" and self.attn_kind == "mla":
                H, r = self.n_heads, self.kv_lora_rank
                total += (d * H * self.qk_head_dim
                          + d * (r + self.qk_rope_head_dim) + r
                          + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                          + H * self.v_head_dim * d + d)
            elif mixer == "attn" or mixer == "xattn":
                qk = d * self.n_heads * self.hd + d * self.n_kv_heads * self.hd * 2
                total += qk + self.n_heads * self.hd * d + d
                if mixer == "xattn":
                    total += qk + self.n_heads * self.hd * d + d
            elif mixer == "ssm":
                di, s, r = self.d_inner, self.ssm_state, self.dt_rank
                total += d * 2 * di + self.ssm_conv * di + di * (r + 2 * s)
                total += r * di + di * s + di + di * d + d
            if ffn == "dense":
                ff = self.dense_d_ff or self.d_ff
                total += d * ff * (3 if self.mlp_gated else 2) + d
            elif ffn == "moe":
                e_ff = self.d_ff
                total += d * self.n_experts + self.experts_held * d * e_ff * 3 + d
                if self.n_shared_experts:
                    total += d * e_ff * self.n_shared_experts * 3
        if self.family == "encdec":
            # encoder layers (self-attn + dense ffn)
            enc = self.n_enc_layers * (
                d * self.n_heads * self.hd * 2 + d * self.n_kv_heads * self.hd * 2
                + (self.d_ff * d * (3 if self.mlp_gated else 2)) + 3 * d)
            total += enc
        return total

    def n_active_params(self) -> int:
        """Params touched per token (MoE: top-k + shared experts only)."""
        if self.n_experts == 0:
            return self.n_params()
        d = self.d_model
        total = self.n_params()
        for mixer, ffn in layer_kinds(self):
            if ffn == "moe":
                inactive = max(self.experts_held - self.experts_per_token, 0) * d * self.d_ff * 3
                total -= inactive
        return total


def layer_kinds(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """Per-layer (mixer, ffn) for the decoder stack.

    mixer ∈ {attn, ssm};  ffn ∈ {dense, moe, none}.  The first
    ``first_k_dense`` layers are attention + dense FFN; the pattern below
    holds for the layers after them.
    """
    kinds = []
    for i in range(cfg.n_layers):
        if i < cfg.first_k_dense:
            kinds.append(("attn", "dense"))
            continue
        if cfg.family == "ssm":
            mixer = "ssm"
        elif cfg.family == "hybrid" and cfg.attn_every:
            mixer = "attn" if i % cfg.attn_every == cfg.attn_offset else "ssm"
        else:
            mixer = "attn"
        if cfg.n_experts and i % cfg.moe_every == cfg.moe_offset:
            ffn = "moe"
        elif cfg.family == "ssm":
            ffn = "none"       # mamba-1 blocks have no separate FFN
        else:
            ffn = "dense"
        kinds.append((mixer, ffn))
    return kinds


def block_period(cfg: ModelConfig) -> int:
    """Smallest p such that the kinds of the layers after the leading dense
    ones repeat with period p, and p divides their count."""
    kinds = layer_kinds(cfg)[cfg.first_k_dense:]
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p:
            continue
        if all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def period_kinds(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """The (mixer, ffn) of each position of the scanned period block."""
    k = cfg.first_k_dense
    return layer_kinds(cfg)[k:k + block_period(cfg)]


def n_blocks(cfg: ModelConfig) -> int:
    """How many period blocks the scan runs."""
    return (cfg.n_layers - cfg.first_k_dense) // block_period(cfg)


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Training / serving run parameters (input shape + distribution)."""
    global_batch: int = 256
    seq_len: int = 4096
    mode: str = "train"              # train | prefill | decode
    # decentralized training
    algorithm: str = "edm"
    alpha: float = 1e-3
    beta: float = 0.9
    topology: str = "ring"           # ring | exp | torus | full | hier
    agents: str = "data"             # data | pod  (DESIGN §3)
    gossip_engine: str = "shifts"    # dense | shifts | ppermute  (DESIGN §3)
    # time-varying gossip (DESIGN §4): static wraps `topology`; round_robin =
    # one-peer exp rounds; alt_hier = intra-pod rounds + one inter-pod round
    gossip_schedule: str = "static"  # static | round_robin | alt_hier
    gossip_period: int = 0           # alt_hier: intra rounds per inter (0→1)
    gossip_seed: int = 0             # round_robin: offset-order shuffle (0=off)
    agents_per_device: int = 1       # blocked ppermute: A > device count (§4)
    # packed parameter bus (DESIGN §5): params + EDM state live in one
    # (A, rows, 128) superbuffer — one edm_update pallas_call and one
    # ppermute per gossip term per step.  None = auto: on for the
    # algorithm="edm" + gossip_engine="ppermute" production path.
    packed_bus: Optional[bool] = None
    # overlapped gossip pipeline (DESIGN §6): "off" = synchronous gossip on
    # the critical path (bit-identical to the plain bus step); "delayed" =
    # one-step-stale mixing — the live payload's permutes are issued before
    # the backward pass and combined after it, so wire time hides behind
    # compute.  Requires the packed bus (the payload is ONE buffer).
    overlap: str = "off"             # off | delayed
    gossip_dtype: str = "float32"    # bf16 payload is a §Perf lever
    # quantized gossip wire (DESIGN §9): wire format of the bus permutes.
    # "bf16" / "int8" route the packed-bus step through the error-feedback
    # codec (bus-shaped residual in the opt state, decode folded into the
    # combine); "f32" is the byte-identical legacy wire.  Packed bus only;
    # mutually exclusive with gossip_dtype != float32 (the codec replaces
    # that cast lever and, unlike it, composes with overlap="delayed").
    wire: str = "f32"                # f32 | bf16 | int8
    gossip_every: int = 1            # gossip every k steps (local-EDM, §Perf)
    # policy groups (DESIGN §12): the single declarative entry point for
    # WHAT gossips, HOW OFTEN and at WHAT precision.  "" = one default
    # "dense" group (bit-identical to the ungrouped bus); presets
    # "moe[:k]" / "ssm[:k]" put expert / conv+SSM-state leaves in their
    # own group (k = that group's gossip_every, 0 = full opt-out); a JSON
    # list gives explicit specs: [{"name": ..., "match": [...],
    # "gossip_every": ..., "wire": ..., "schedule": ...}, ...].
    # Parsed by repro.train.trainer.resolve_group_specs.
    gossip_groups: str = ""
    moe_sharding: bool = False       # explicit MoE dispatch constraints (§Perf)
    moe_impl: str = "gspmd"          # gspmd | shard_map  (§Perf serving path)
    attn_bf16_path: bool = False     # bf16 attention data path (§Perf)
    remat: bool = True
    remat_policy: str = "full"       # full | dots  (§Perf)
    seq_parallel: bool = False       # sequence-sharded residual (§Perf)
    warmup_steps: int = 0            # LR schedule (0 = constant α)
    total_steps: int = 0
    # serving
    decode_window: int = 0           # 0 → full KV cache; else sliding window


# the four assigned input shapes ------------------------------------------------
INPUT_SHAPES = {
    "train_4k":    RunConfig(global_batch=256, seq_len=4096,   mode="train"),
    "prefill_32k": RunConfig(global_batch=32,  seq_len=32768,  mode="prefill"),
    "decode_32k":  RunConfig(global_batch=128, seq_len=32768,  mode="decode"),
    "long_500k":   RunConfig(global_batch=1,   seq_len=524288, mode="decode",
                             decode_window=8192),
}


def reduced(cfg: ModelConfig, n_layers: int = 2, d_model: int = 256,
            vocab: int = 512) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests (≤4 experts etc.)."""
    period = block_period(cfg)
    n_layers = max(n_layers, period)
    n_layers = (n_layers + period - 1) // period * period + cfg.first_k_dense
    n_heads = min(cfg.n_heads, 4) if cfg.n_heads else 0
    n_kv = min(cfg.n_kv_heads, n_heads) if cfg.n_kv_heads else 0
    if n_kv and cfg.n_kv_heads == cfg.n_heads:
        n_kv = n_heads  # keep MHA archs MHA
    updates = dict(
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=min(cfg.d_model, d_model),
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=64 if cfg.n_heads else 0,
        d_ff=min(cfg.d_ff, 2 * d_model) if cfg.d_ff else 0,
        dense_d_ff=min(cfg.dense_d_ff, 2 * d_model) if cfg.dense_d_ff else 0,
        vocab_size=min(cfg.vocab_size, vocab),
        n_experts=min(cfg.n_experts, 4),
        n_experts_held=min(cfg.n_experts_held, 2),
        expert_first=min(cfg.expert_first, 2),
        experts_per_token=min(cfg.experts_per_token, 2),
        # dropless at smoke scale (C ≥ T·k/E · E/k): prefill↔decode must agree
        capacity_factor=8.0,
        n_shared_experts=min(cfg.n_shared_experts, 1),
        ssm_state=min(cfg.ssm_state, 16),
        ssm_dt_rank=8 if cfg.ssm_state else 0,
        n_enc_layers=min(cfg.n_enc_layers, 2),
        n_frontend_tokens=min(cfg.n_frontend_tokens, 16),
        attn_every=min(cfg.attn_every, n_layers) if cfg.attn_every else 0,
        attn_offset=min(cfg.attn_offset, min(cfg.attn_every, n_layers) - 1)
        if cfg.attn_every else 0,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        kv_lora_rank=min(cfg.kv_lora_rank, 64),
        qk_nope_head_dim=min(cfg.qk_nope_head_dim, 32),
        qk_rope_head_dim=min(cfg.qk_rope_head_dim, 16),
        v_head_dim=min(cfg.v_head_dim, 32),
        rope_original_max_pos=min(cfg.rope_original_max_pos, 64),
        dtype="float32",
    )
    return dataclasses.replace(cfg, **updates)
