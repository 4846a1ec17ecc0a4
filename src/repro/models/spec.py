"""Architecture specification.

A model is a sequence of *blocks* (mixer + ffn), plus embedding/head and an
optional non-pipelined frontend (audio frames / vision patches / encoder).

PipeDream requirement: blocks are grouped into ``pp`` contiguous stages.
Because the pipeline is SPMD (every stage executes the same program), the
*kind pattern* of blocks inside each stage must be identical across stages;
per-layer scalars that differ (attention window, rope theta) travel as data
arrays of shape [pp, layers_per_stage] instead of static attributes.
Configs choose pp so this holds (validated by ``validate_stageability``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

GLOBAL_WINDOW = -1  # window sentinel: full causal attention


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_expert: int              # ffn width per expert
    n_shared: int = 0          # shared (always-on) experts
    d_shared: int = 0          # ffn width of the shared expert(s)
    router_aux_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0           # 0 -> ceil(d_model/16)


@dataclasses.dataclass(frozen=True)
class RWKVSpec:
    head_dim: int = 64
    decay_lora: int = 64       # rank of the data-dependent decay LoRA
    tmix_lora: int = 32        # rank of the token-shift mix LoRA


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """Non-pipelined encoder (whisper). Runs tensor-sharded before the pipe."""

    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    source_len: int            # frames after the (stubbed) conv frontend


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str = "attn"        # attn | mamba | rwkv | none
    ffn: str = "dense"         # dense | moe | rwkv_cmix | none
    window: int = GLOBAL_WINDOW
    rope_theta: float = 1e4
    cross_attn: bool = False   # decoder cross-attention (whisper)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    blocks: Tuple[BlockSpec, ...]
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    act: str = "silu"          # silu | gelu
    qk_norm: bool = False
    rope_2d: bool = False      # chatglm-style half-rotary
    moe: Optional[MoESpec] = None
    mamba: Optional[MambaSpec] = None
    rwkv: Optional[RWKVSpec] = None
    encoder: Optional[EncoderSpec] = None
    frontend: str = "none"     # none | audio | vision
    n_patches: int = 0         # vision frontend: patch tokens per sample
    tie_embeddings: bool = False
    family: str = "dense"      # dense | moe | ssm | hybrid | vlm | audio
    subquadratic: bool = False # eligible for long_500k

    def __post_init__(self):
        assert len(self.blocks) == self.n_layers, (len(self.blocks), self.n_layers)
        assert self.norm in ("rmsnorm", "layernorm")
        assert self.act in ("silu", "gelu")

    # ---- stage decomposition -------------------------------------------------

    def with_depth(self, n_layers: int) -> "ModelSpec":
        """The same widths cut to the first ``n_layers`` blocks."""
        assert 1 <= n_layers <= self.n_layers, (n_layers, self.n_layers)
        return dataclasses.replace(self, n_layers=n_layers,
                                   blocks=self.blocks[:n_layers])

    def layers_per_stage(self, pp: int) -> int:
        assert self.n_layers % pp == 0, (
            f"{self.name}: pp={pp} must divide n_layers={self.n_layers}")
        return self.n_layers // pp

    def stage_program(self, pp: int) -> Tuple[BlockSpec, ...]:
        """The (validated) per-stage block pattern."""
        validate_stageability(self, pp)
        return self.blocks[: self.layers_per_stage(pp)]

    # ---- bookkeeping ---------------------------------------------------------

    @property
    def d_attn(self) -> int:
        return self.n_heads * self.d_head

    def param_count(self) -> int:
        """Exact parameter count (embedding + blocks + head + norms)."""
        n = self.vocab * self.d_model                       # embed
        if not self.tie_embeddings:
            n += self.vocab * self.d_model                  # head
        n += self.d_model                                   # final norm
        for b in self.blocks:
            n += _block_params(self, b)
        if self.encoder is not None:
            e = self.encoder
            per = (4 * e.d_model * e.d_model + 2 * e.d_model * e.d_ff
                   + 4 * e.d_model)
            n += e.n_layers * per + e.d_model
        return n

    def active_param_count(self) -> int:
        """Params active per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        dense_total = self.param_count()
        per_expert = 3 * self.d_model * m.d_expert
        n_moe_blocks = sum(1 for b in self.blocks if b.ffn == "moe")
        inactive = n_moe_blocks * per_expert * (m.n_experts - m.top_k)
        return dense_total - inactive


def _block_params(spec: ModelSpec, b: BlockSpec) -> int:
    n = 0
    d = spec.d_model
    if b.mixer == "attn":
        n += d * spec.d_attn + 2 * d * spec.n_kv * spec.d_head + spec.d_attn * d
        n += d  # mixer norm
        if spec.qk_norm:
            n += 2 * spec.d_head
        if b.cross_attn:
            n += d * spec.d_attn + 2 * d * spec.n_kv * spec.d_head + spec.d_attn * d + d
    elif b.mixer == "mamba":
        ms = spec.mamba
        d_in = ms.expand * d
        dt_rank = ms.dt_rank or -(-d // 16)
        n += d * 2 * d_in                      # in_proj (x, z)
        n += d_in * ms.d_conv                  # conv
        n += d_in * (dt_rank + 2 * ms.d_state)  # x -> dt, B, C
        n += dt_rank * d_in + d_in             # dt proj + bias
        n += d_in * ms.d_state + d_in          # A_log, D
        n += d_in * d                          # out proj
        n += d                                 # norm
    elif b.mixer == "rwkv":
        rs = spec.rwkv
        n += 4 * d * d                         # r, k, v, g
        n += d * d                             # output
        n += 5 * d + d * rs.tmix_lora * 2 * 5  # token-shift maa + lora
        n += d * rs.decay_lora + rs.decay_lora * d + d  # decay lora + u
        n += 2 * d                             # group norm
        n += d                                 # block norm
    if b.ffn == "dense":
        n += 3 * d * spec.d_ff if spec.act == "silu" else 2 * d * spec.d_ff
        n += d
    elif b.ffn == "moe":
        m = spec.moe
        n += m.n_experts * 3 * d * m.d_expert
        n += d * m.n_experts                   # router
        n += m.n_shared * 3 * d * m.d_shared
        n += d
    elif b.ffn == "rwkv_cmix":
        n += d * int(3.5 * d) + int(3.5 * d) * d + 2 * d  # wide k + v proj + maa
        n += d
    return n


def validate_stageability(spec: ModelSpec, pp: int) -> None:
    """Every stage must run the identical block-kind program."""
    lps = spec.layers_per_stage(pp)
    pattern = [(b.mixer, b.ffn, b.cross_attn) for b in spec.blocks[:lps]]
    for s in range(1, pp):
        got = [(b.mixer, b.ffn, b.cross_attn)
               for b in spec.blocks[s * lps:(s + 1) * lps]]
        assert got == pattern, (
            f"{spec.name}: stage {s} block pattern {got} != stage 0 {pattern}; "
            f"choose a pp that aligns with the layer-type period")


def stage_varying_scalars(spec: ModelSpec, pp: int):
    """Per-layer scalars that differ across stages, as [pp, lps] lists."""
    lps = spec.layers_per_stage(pp)
    windows = [[spec.blocks[s * lps + i].window for i in range(lps)]
               for s in range(pp)]
    thetas = [[spec.blocks[s * lps + i].rope_theta for i in range(lps)]
              for s in range(pp)]
    return windows, thetas
