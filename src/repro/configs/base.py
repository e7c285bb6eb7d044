"""Config system: architecture configs + the sub-model training config.

Every assigned architecture gets a ``ModelConfig`` in its own module under
``repro.configs``; the registry maps ``--arch <id>`` to it.  ``reduced()``
produces the CPU smoke-test variant of the same family (<=2 layers,
d_model<=512, <=4 experts) required by the brief.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden width
    n_shared: int = 0              # shared (always-on) experts
    router: str = "softmax"        # "softmax" (mixtral) | "sigmoid" (deepseek-v3)
    capacity_factor: float = 1.25  # dispatch capacity factor
    aux_loss_weight: float = 0.01  # load-balance loss weight
    # Router width: the experts of the whole layer, of which this model
    # holds ``n_experts`` (ids 0 .. n_experts-1, the first share of a
    # contiguous expert split).  0 = ``n_experts``: the layer is whole.
    router_experts: int = 0
    # softmax router: True takes the softmax over the top-k logits (the
    # chosen weights sum to 1); False keeps the top-k probabilities of the
    # softmax over every expert as they are (DeepSeek-V2).
    norm_topk_prob: bool = True
    aux_loss: str = "switch"       # "switch" (top-1) | "seq" (DeepSeek-V2)

    @property
    def n_router(self) -> int:
        return self.router_experts or self.n_experts

    @property
    def is_share(self) -> bool:
        """True when this model holds a share of a wider expert layer."""
        return self.n_router != self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    d_state: int                   # SSD state size N
    head_dim: int = 64             # P
    n_heads: int = 0               # derived if 0: expand*d_model // head_dim
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256               # SSD chunk length


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536        # 0 = no q compression: wq [D, H, qh]
    kv_lora_rank: int = 512
    rope_head_dim: int = 64        # decoupled rope dims (shared k_rope)
    nope_head_dim: int = 128
    v_head_dim: int = 128
    # YaRN rope scaling of the decoupled rope dims (DeepSeek-V2's
    # ``rope_scaling``); factor 0 = plain rope.
    yarn_factor: float = 0.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # derived if 0: d_model // n_heads
    norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    pos_embed: str = "rope"        # rope | sinusoidal | none
    qk_norm: bool = False
    sliding_window: int = 0        # 0 = full attention
    tie_embeddings: bool = False
    act: str = "silu"
    # family extensions
    moe: Optional[MoEConfig] = None
    n_dense_layers: int = 0        # leading dense layers before MoE layers
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    hybrid: bool = False           # parallel attn + ssm heads per layer (hymba)
    mtp: bool = False              # deepseek multi-token-prediction head
    n_codebooks: int = 0           # musicgen: EnCodec codebook streams
    vision_stub: bool = False      # phi-3-vision: patch-embedding frontend
    vision_d: int = 1024           # stub patch-embedding width
    vision_patches: int = 256      # patches prepended to the tokens
    source: str = ""               # citation

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    def n_params(self) -> int:
        """Approximate parameter count (embedding + blocks)."""
        D, F, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        hd = self.head_dim
        n = V * D * (1 if self.tie_embeddings else 2)
        if self.n_codebooks:
            n += self.n_codebooks * V * D  # extra heads
        per = 0
        if not self.attn_free:
            if self.mla is not None:
                m = self.mla
                qh = m.nope_head_dim + m.rope_head_dim
                if m.q_lora_rank:
                    per += D * m.q_lora_rank + m.q_lora_rank * self.n_heads * qh
                else:
                    per += D * self.n_heads * qh
                per += D * (m.kv_lora_rank + m.rope_head_dim)
                per += m.kv_lora_rank * self.n_heads * (m.nope_head_dim + m.v_head_dim)
                per += self.n_heads * m.v_head_dim * D
            else:
                per += D * self.n_heads * hd + 2 * D * self.n_kv_heads * hd
                per += self.n_heads * hd * D
        if self.ssm is not None:
            s = self.ssm
            nh = s.n_heads or (s.expand * D) // s.head_dim
            d_in = nh * s.head_dim
            per += D * (2 * d_in + 2 * s.d_state * nh + nh) + d_in * D
            per += s.conv_width * (d_in + 2 * s.d_state * nh)
        if self.moe is not None:
            mo = self.moe
            n_moe = L - self.n_dense_layers
            per_moe = (mo.n_experts + mo.n_shared) * 3 * D * mo.d_ff + D * mo.n_router
            n += n_moe * per_moe + self.n_dense_layers * 3 * D * F
            n += L * per + 2 * L * D
            return n
        if F:
            per += 3 * D * F
        n += L * per + 2 * L * D
        return n

    def n_active_params(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.n_params()
        mo = self.moe
        full = self.n_params()
        n_moe = self.n_layers - self.n_dense_layers
        all_e = (mo.n_experts + mo.n_shared) * 3 * self.d_model * mo.d_ff
        act_e = (mo.top_k + mo.n_shared) * 3 * self.d_model * mo.d_ff
        return full - n_moe * (all_e - act_e)


# ---------------------------------------------------------------------------
# Sub-model training (the paper's technique) run config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SubmodelConfig:
    """Configuration of distributed sub-model training (Alg. 1 / Alg. 2).

    The one object that fixes a round's sub-model plan: which semantic
    ``axes`` are windowed, the per-axis ``capacity`` fraction, the
    selection ``scheme`` (``rolling`` is the paper's shuffled Algorithm 2;
    ``bernoulli`` the unstructured Algorithm 1), K ``local_steps``, C
    ``clients_per_round``, and the client/server learning rates.  Consumed
    by :func:`repro.api.fed_round`::

        scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
                              local_steps=2, clients_per_round=16,
                              stagger=True)       # per-client windows
        fed = api.fed_round(model, scfg)

    ``stagger=True`` rotates the rolling window per client (full axis
    coverage every round — beyond-paper); ``align`` rounds window sizes
    and offsets to hardware-friendly multiples (128 on TPU keeps every
    fused-kernel block dense MXU work); ``wrap`` enables FedRolex
    wraparound windows (dense-mask mode).  See ``docs/paper_map.md`` for
    the paper symbol ↔ field mapping.
    """

    scheme: str = "rolling"        # rolling | random | static | full
    capacity: float = 0.5          # beta: fraction of each maskable axis
    # which semantic axes are windowed; others stay full
    axes: Tuple[str, ...] = ("d_ff", "heads", "kv_heads", "experts",
                             "ssm_heads", "moe_d_ff")
    local_steps: int = 2           # K
    clients_per_round: int = 16    # C, laid out on the mesh `data` axis
    client_lr: float = 0.05        # eta
    server_lr: float = 1.0
    proj_radius: float = 0.0       # W: l2 projection radius (0 = off)
    seed: int = 0
    wrap: bool = False             # FedRolex wraparound windows (small models)
    align: int = 1                 # round window sizes/offsets to multiples
    stagger: bool = False          # rolling: rotate window per client (beyond-paper)
    # Window-mode aggregation fast path: average sub-model deltas then do a
    # single scatter when every client trains the same window.  None derives
    # it from the scheme (rolling/static/importance without stagger); False
    # forces the per-client scatter baseline (the old REPRO_NO_SHARED_WINDOW
    # env knob, now only a documented default in launch/train.py).
    shared_window: Optional[bool] = None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCHS = [
    "deepseek_v3_671b", "tinyllama_1_1b", "mamba2_130m", "musicgen_large",
    "qwen3_14b", "deepseek_7b", "mixtral_8x22b", "qwen3_32b",
    "phi_3_vision_4_2b", "hymba_1_5b", "resnet18_cifar",
]

_ALIAS = {a.replace("_", "-"): a for a in ARCHS}


def get_config(arch: str) -> ModelConfig:
    arch = _ALIAS.get(arch, arch).replace("-", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def get_reduced_config(arch: str) -> ModelConfig:
    """CPU smoke-test variant: <=2 layers, d_model<=512, <=4 experts."""
    arch = _ALIAS.get(arch, arch).replace("-", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.reduced()


def list_archs():
    return [a for a in ARCHS if a != "resnet18_cifar"]


def _shrink(cfg: ModelConfig, **over) -> ModelConfig:
    """Generic reduction preserving the family structure."""
    base = dict(
        n_layers=2,
        d_model=min(cfg.d_model, 256),
        n_heads=min(cfg.n_heads, 8),
        n_kv_heads=min(cfg.n_kv_heads, 4),
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab=min(cfg.vocab, 512),
        head_dim=32,
        vision_patches=min(cfg.vision_patches, 16),
        vision_d=min(cfg.vision_d, 64),
    )
    if cfg.moe is not None:
        base["moe"] = replace(cfg.moe, n_experts=min(cfg.moe.n_experts, 4),
                              top_k=min(cfg.moe.top_k, 2),
                              d_ff=min(cfg.moe.d_ff, 256))
        base["n_dense_layers"] = min(cfg.n_dense_layers, 1)
    if cfg.ssm is not None:
        base["ssm"] = replace(cfg.ssm, d_state=min(cfg.ssm.d_state, 16),
                              head_dim=32, chunk=32)
    if cfg.mla is not None:
        base["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=64,
                                rope_head_dim=16, nope_head_dim=32,
                                v_head_dim=32)
    base.update(over)
    return replace(cfg, name=cfg.name + "-reduced", **base)
