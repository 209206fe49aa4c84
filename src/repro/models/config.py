"""Model architecture configuration.

One frozen dataclass describes every architecture in the assigned pool:
dense decoders, GQA/MQA, sliding-window attention, MoE (coarse + fine
grained), recurrent-hybrid (RG-LRU), xLSTM, encoder-only audio and
VLM-backbone models.  Per-arch instances live in :mod:`repro.configs`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """A routed expert layer.

    ``n_experts`` is the router's width: every token is routed over all
    of them, ``top_k`` at a time.  ``norm_topk`` rescales the ``top_k``
    gate probabilities to sum to one (Mixtral); with it off they stay
    the softmax's own (DeepSeek's ``norm_topk_prob: false``).

    ``held = (i, n)`` says which experts this layer holds: chip ``i`` of
    ``n`` sharing the layer under expert parallelism holds the contiguous
    range ``i * E / n ... (i + 1) * E / n - 1``.  Its parameters are
    those experts alone; the layer computes their part of the result for
    the tokens routed to them, and the part of the absent experts is left
    out (the chips that hold them add it).  ``(0, 1)`` holds them all.
    """
    n_experts: int
    top_k: int
    d_expert: int                 # ffn width per routed expert
    n_shared: int = 0             # always-on shared experts (DeepSeek-MoE)
    d_shared: int = 0             # ffn width of the fused shared expert
    capacity_factor: float = 1.25  # the all-to-all dispatch's queues only
    first_dense: int = 0          # leading dense layers (DeepSeek-MoE: 1)
    d_first_dense: int = 0        # ffn width of those dense layers
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    norm_topk: bool = True        # renormalise the top-k gate weights
    held: Tuple[int, int] = (0, 1)   # (chip i, of n chips) -- see above

    @property
    def n_held(self) -> int:
        i, n = self.held
        assert 0 <= i < n and self.n_experts % n == 0, (self.held,
                                                        self.n_experts)
        return self.n_experts // n

    @property
    def first_held(self) -> int:
        return self.held[0] * self.n_held


@dataclass(frozen=True)
class YarnRope:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it:
    the rope frequencies ramp from interpolated (``/ factor``) to the
    original between the ``beta_fast`` and ``beta_slow`` correction
    dimensions of ``original_max_position_embeddings``; attention logits
    scale by ``m(mscale_all_dim) ** 2`` and cos/sin by ``m(mscale) /
    m(mscale_all_dim)``, where ``m(s) = 0.1 * s * ln(factor) + 1``."""
    factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_position_embeddings: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | encoder | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    cycle: Tuple[str, ...] = ("attn",)   # block kinds, cycled over layers
    prefix: Tuple[str, ...] = ()         # unscanned leading blocks
    window: Optional[int] = None          # sliding-window size (SWA / local)
    moe: Optional[MoEConfig] = None
    act: str = "swiglu"           # swiglu | geglu | gelu
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    norm_eps: float = 1e-5
    causal: bool = True
    rope_theta: float = 10_000.0
    # multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), on when
    # kv_lora_rank > 0: keys and values come from one normalised latent
    # of kv_lora_rank per token, plus one rope key of qk_rope_head_dim
    # shared by every head; a head's query is qk_nope_head_dim +
    # qk_rope_head_dim wide, its value v_head_dim.  No query compression.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional[YarnRope] = None       # rope scaling (MLA's rope key)
    tie_embeddings: bool = False
    parallel_residual: bool = False       # Cohere-style parallel attn+mlp
    frontend: Optional[str] = None        # None | "audio" | "vision"
    n_patches: int = 256          # vision stub: patch embeddings per image
    # recurrent-block hyper-params
    rnn_width: int = 0            # RG-LRU recurrence width (0 -> d_model)
    conv_width: int = 4           # temporal conv in the recurrent block
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    notes: str = ""

    # ------------------------------------------------------------- derived
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def q_dim(self) -> int:
        """Width of the attention output that the out-projection reads."""
        if self.mla:
            return self.n_heads * self.v_head_dim
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    @property
    def prefix_kinds(self) -> Tuple[str, ...]:
        """Unscanned leading blocks: explicit ``prefix`` or, for MoE
        configs, the leading dense layers (DeepSeek's first_dense)."""
        if self.prefix:
            return self.prefix
        if self.moe is not None and self.moe.first_dense:
            return tuple(self.cycle[i % len(self.cycle)]
                         for i in range(self.moe.first_dense))
        return ()

    def block_kind(self, layer: int) -> str:
        npfx = len(self.prefix_kinds)
        if layer < npfx:
            return self.prefix_kinds[layer]
        return self.cycle[(layer - npfx) % len(self.cycle)]

    @property
    def blocks(self) -> Tuple[str, ...]:
        return tuple(self.block_kind(i) for i in range(self.n_layers))

    @property
    def n_cycles(self) -> int:
        n = self.n_layers - len(self.prefix_kinds)
        assert n % len(self.cycle) == 0, (
            f"{self.name}: {n} cycled layers not a multiple of "
            f"cycle {self.cycle}")
        return n // len(self.cycle)

    @property
    def cycle_groups(self) -> Tuple[Tuple[str, int], ...]:
        """Run-length-encoded cycle: consecutive identical block kinds are
        executed as an inner scan over stacked parameters, so XLA
        allocates each kind's working buffers once per group instead of
        once per block (decisive for xLSTM's 7x mLSTM cycle)."""
        groups = []
        for k in self.cycle:
            if groups and groups[-1][0] == k:
                groups[-1][1] += 1
            else:
                groups.append([k, 1])
        return tuple((k, c) for k, c in groups)

    @property
    def is_decoder(self) -> bool:
        return self.causal

    @property
    def subquadratic(self) -> bool:
        """True if decode-state size is O(window + rnn_state), i.e. the
        arch can serve 500k-token contexts (SWA / recurrent / local-attn)."""
        kinds = set(self.blocks)
        if kinds & {"rglru", "mlstm", "slstm"}:
            full_attn = ("attn" in kinds and self.window is None)
            return not full_attn
        return self.window is not None

    # rough parameter count (embedding + blocks), for sanity checks
    def param_count(self) -> int:
        d = self.d_model
        n = 0
        n += self.vocab * d                      # embed
        if not self.tie_embeddings:
            n += self.vocab * d                  # lm head
        for i, kind in enumerate(self.blocks):
            n += 2 * d                           # 2 norms
            if kind in ("attn", "local_attn") and self.mla:
                r, rope = self.kv_lora_rank, self.qk_rope_head_dim
                n += d * self.n_heads * (self.qk_nope_head_dim + rope)
                n += d * (r + rope) + r
                n += r * self.n_heads * (self.qk_nope_head_dim
                                         + self.v_head_dim)
                n += self.q_dim * d
            elif kind in ("attn", "local_attn"):
                n += d * self.q_dim + self.q_dim * d + 2 * d * self.kv_dim
            elif kind == "rglru":
                w = self.rnn_width or d
                n += 2 * d * w + w * d           # in x2, out
                n += self.conv_width * w + 3 * w # conv + gates-ish
            elif kind in ("mlstm",):
                w = int(d * self.mlstm_proj_factor)
                n += 2 * d * w + w * d + 3 * w * (w // max(self.n_heads, 1))
            elif kind == "slstm":
                w = int(d * self.slstm_proj_factor)
                n += 4 * d * d + 2 * d * w
            if kind in ("attn", "local_attn"):
                n += self._mlp_params(i < len(self.prefix_kinds))
        return n

    def _mlp_params(self, dense: bool = False) -> int:
        d = self.d_model
        if self.moe is not None and dense and self.moe.d_first_dense:
            per = 3 if self.act in ("swiglu", "geglu") else 2
            return per * d * self.moe.d_first_dense
        if self.moe is not None:
            m = self.moe
            per = (3 if self.act in ("swiglu", "geglu") else 2)
            n = m.n_held * per * d * m.d_expert + d * m.n_experts
            if m.n_shared:
                n += per * d * m.d_shared
            return n
        per = 3 if self.act in ("swiglu", "geglu") else 2
        return per * d * self.d_ff


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment."""

    name: str                     # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                     # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Skip rules from the assignment (documented in DESIGN.md)."""
    if not cfg.is_decoder and shape.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, ("pure full-attention arch; 500k decode needs "
                       "sub-quadratic attention")
    return True, ""
