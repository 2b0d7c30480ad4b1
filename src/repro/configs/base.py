"""Architecture config system: one frozen dataclass drives model
construction, sharding rules, dry-run input specs and roofline math.

``--arch <id>`` resolves through the registry (``get_config``); each
assigned architecture lives in its own module citing its source.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    """Pad vocab to a shardable multiple (logits over padding ids are
    never produced as labels). Kept import-free: configs must not import
    model code (model modules import configs)."""
    return ((vocab + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    qkv_bias: bool = False
    qk_norm: bool = False
    activation: str = "silu"        # silu | gelu | relu2
    gated_mlp: bool = True
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    rope_theta: float = 1e4
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_group_size: int = 512
    moe_capacity_factor: float = 1.25
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # dt = softplus(.) is clipped to this range (Mamba2's time_step_limit)
    ssm_dt_limit: tuple[float, float] = (1e-4, 1e2)
    # hybrid (zamba2): one shared attention+MLP block applied every
    # ``attn_every`` SSM layers (tied weights)
    attn_every: int = 0
    # hybrid by layer pattern (granite-4.0-h): the mixer of each layer,
    # "mamba" or "attention", each with weights of its own and followed
    # by the gated MLP. Empty for every other family.
    layer_types: tuple[str, ...] = ()
    # Granite's scalings: the input embeddings are multiplied by
    # ``embedding_multiplier``, each residual branch by
    # ``residual_multiplier``, the logits divided by ``logits_scaling``;
    # ``attention_multiplier`` (when set) replaces 1/sqrt(head_dim).
    # Read by the layer-pattern path only.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    positions: str = "rope"         # rope | nope (no positional encoding)
    tie_embeddings: bool = False    # the output head is the embedding
    norm_eps: float = 1e-6
    # enc-dec (whisper)
    encoder_layers: int = 0
    n_frames: int = 1500            # encoder source positions (stub frontend)
    # attention
    window: Optional[int] = None    # sliding-window attention (SWA)
    long_context_window: int = 4096  # window used for long_500k dense variant
    decode_buffer: int = 256        # replicated decode write-buffer slots
    # numerics
    dtype: str = "bfloat16"
    remat: bool = True
    # gradient-accumulation microbatches for train_4k on the production
    # mesh — sized per arch so the remat-saved per-layer stacks fit
    # 16 GiB/chip (EXPERIMENTS.md §Dry-run)
    train_microbatches: int = 4
    # Adam moment storage dtype; "bfloat16" halves optimizer HBM (used by
    # qwen3-moe-235b to fit one pod — EXPERIMENTS.md §Perf HC2)
    adam_moment_dtype: str = "float32"
    # citation
    source: str = ""

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Archs running long_500k natively (sub-quadratic / O(1) state or
        native SWA); dense archs run it via the SWA variant."""
        return self.family in ("ssm", "hybrid") or self.window is not None

    def param_count(self) -> int:
        """Approximate parameter count (used for roofline MODEL_FLOPS)."""
        d, f, L = self.d_model, self.d_ff, self.n_layers
        emb = self.padded_vocab * d * 2  # embed + lm_head (untied)
        per_layer = 0
        if self.layer_types:
            return self._pattern_params()
        if self.family == "ssm":
            per_layer = self._ssm_block_params()
        elif self.family == "hybrid":
            per_layer = self._ssm_block_params()
            n_shared = L // max(self.attn_every, 1)
            shared = (self._attn_params() + 3 * d * f + 2 * d)
            return emb + L * per_layer + shared + n_shared * 0 + 2 * d
        else:
            per_layer += self._attn_params()
            if self.n_experts:
                per_layer += d * self.n_experts  # router
                mult = 3 if self.gated_mlp else 2
                per_layer += self.n_experts * mult * d * f
            else:
                mult = 3 if self.gated_mlp else 2
                per_layer += mult * d * f
            per_layer += 2 * d  # norms
        total = emb + L * per_layer + d
        if self.encoder_layers:
            enc_layer = self._attn_params() + 2 * d * f + 2 * d
            total += self.encoder_layers * enc_layer
            total += L * (self._attn_params() + d)  # decoder cross-attn
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if not self.n_experts:
            return self.param_count()
        d, f, L = self.d_model, self.d_ff, self.n_layers
        mult = 3 if self.gated_mlp else 2
        dense_like = self.param_count() - L * self.n_experts * mult * d * f
        return dense_like + L * self.top_k * mult * d * f

    def _attn_params(self) -> int:
        d = self.d_model
        return d * self.q_dim * 2 + d * self.kv_dim * 2

    def _pattern_params(self) -> int:
        """Parameters of the layer-pattern hybrid: per layer its mixer
        (with its norm) and the gated MLP (with its norm)."""
        d, di, N, H = self.d_model, self.d_inner, self.ssm_state, self.ssm_heads
        conv_dim = di + 2 * N
        mamba = (d * (2 * di + 2 * N + H) + conv_dim * (self.ssm_conv + 1)
                 + 3 * H + di + di * d + d)
        attn = self._attn_params() + d
        mlp = (3 if self.gated_mlp else 2) * d * self.d_ff + d
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        n_attn = self.layer_types.count("attention")
        return (emb + d + (len(self.layer_types) - n_attn) * mamba
                + n_attn * attn + len(self.layer_types) * mlp)

    def _ssm_block_params(self) -> int:
        d = self.d_model
        di = self.d_inner
        proj_in = d * (2 * di + 2 * self.ssm_state + self.ssm_heads)
        return proj_in + di * d + (di + 2 * self.ssm_state) * self.ssm_conv + 3 * self.ssm_heads + di + 2 * d


ARCHS: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    ARCHS[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def list_archs() -> list[str]:
    return sorted(ARCHS)


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """Reduced variant of the same family for CPU smoke tests
    (2 layers, d_model <= 512, <= 4 experts)."""
    small: dict = dict(
        n_layers=2,
        d_model=min(cfg.d_model, 256),
        n_heads=min(cfg.n_heads, 4),
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=64,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab=min(cfg.vocab, 1024),
        name=cfg.name + "-smoke",
    )
    if cfg.n_experts:
        small["n_experts"] = min(cfg.n_experts, 4)
        small["top_k"] = min(cfg.top_k, 2)
        small["moe_group_size"] = 64
        # capacity = group: no token ever dropped, so prefill/decode are
        # bitwise-consistent with the full forward in the smoke tests
        small["moe_capacity_factor"] = (small["n_experts"]
                                        / max(small["top_k"], 1))
    if cfg.ssm_state:
        small["ssm_state"] = min(cfg.ssm_state, 32)
        small["ssm_head_dim"] = 32
        small["ssm_chunk"] = 16
    if cfg.attn_every:
        small["attn_every"] = 1
        small["n_kv_heads"] = small["n_heads"]
    if cfg.layer_types:
        # one layer of each kind, in the published order of first use
        small["layer_types"] = tuple(dict.fromkeys(cfg.layer_types))
    if cfg.encoder_layers:
        small["encoder_layers"] = 2
        small["n_frames"] = 16
    if cfg.window is not None:
        small["window"] = 64
    small["decode_buffer"] = 8      # exercise flush_recent in smoke tests
    if cfg.n_kv_heads == cfg.n_heads:  # MHA archs stay MHA
        small["n_kv_heads"] = small["n_heads"]
    small["dtype"] = "float32"
    small.update(overrides)
    return dataclasses.replace(cfg, **small)


# a layer-pattern hybrid's published config.json key -> ArchConfig field
_HYBRID_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "layer_types": "layer_types", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "shared_intermediate_size": "d_ff",
    "vocab_size": "vocab", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_d_conv": "ssm_conv",
    "mamba_expand": "ssm_expand", "mamba_chunk_size": "ssm_chunk",
    "attention_multiplier": "attention_multiplier",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "rms_norm_eps": "norm_eps",
    "position_embedding_type": "positions",
    "tie_word_embeddings": "tie_embeddings",
    "time_step_limit": "ssm_dt_limit",
}


def hybrid_config(cfg: ArchConfig) -> dict:
    """A layer-pattern hybrid's sizes under the names of its published
    ``config.json`` (granitemoehybrid), with the dt clip as
    ``time_step_limit``: what ``repro.models.hybrid_ref`` reads."""
    if not cfg.layer_types:
        raise ValueError(f"{cfg.name} is not a hybrid by layer pattern")
    out = {k: getattr(cfg, f) for k, f in _HYBRID_KEYS.items()}
    out.update(layer_types=list(cfg.layer_types),
               time_step_limit=list(cfg.ssm_dt_limit),
               mamba_n_heads=cfg.ssm_heads, mamba_n_groups=1)
    return out


def from_hybrid_config(c: dict, base: ArchConfig) -> ArchConfig:
    """``base`` with the sizes of a published layer-pattern hybrid config
    ``c`` (the names ``hybrid_config`` gives)."""
    if c["mamba_n_groups"] != 1 or c["mamba_n_heads"] * c["mamba_d_head"] \
            != c["mamba_expand"] * c["hidden_size"]:
        raise ValueError("the Mamba2 here has one group, and heads that "
                         "tile hidden_size x mamba_expand")
    fields = {f: c[k] for k, f in _HYBRID_KEYS.items()}
    fields.update(layer_types=tuple(c["layer_types"]),
                  ssm_dt_limit=tuple(float(x) for x in c["time_step_limit"]),
                  head_dim=c["hidden_size"] // c["num_attention_heads"])
    return dataclasses.replace(base, **fields)
