"""Config dataclasses + registry for all assigned architectures.

Every architecture file in this package registers:
  - its FULL config (exact paper/source numbers; exercised only via the
    dry-run with ShapeDtypeStruct — never allocated on CPU), and
  - a SMOKE config (same family, tiny dims) that runs a real forward/train
    step on CPU in the per-arch smoke tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    max_seq: int = 8192
    rope_theta: float = 10000.0
    # MoE (None => dense)
    moe_experts: Optional[int] = None
    moe_top_k: int = 8
    moe_shared_experts: int = 0
    moe_d_ff: Optional[int] = None          # per-expert hidden dim
    first_dense_layers: int = 0     # e.g. deepseek: first k layers dense
    # MLA (None => GQA)
    mla: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # numerics / schedule
    dtype: Any = jnp.bfloat16
    remat: bool = True
    tie_embeddings: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def family(self) -> str:
        return "lm"


@dataclass(frozen=True)
class VisionConfig:
    name: str
    img_res: int
    patch: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    n_classes: int = 1000
    # Swin-specific
    swin: bool = False
    window: int = 7
    depths: tuple = ()
    dims: tuple = ()
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @property
    def family(self) -> str:
        return "vision"


@dataclass(frozen=True)
class DiffusionConfig:
    name: str
    img_res: int
    patch: int = 2
    latent_channels: int = 4
    n_layers: int = 0                # DiT
    n_double_blocks: int = 0         # MMDiT
    n_single_blocks: int = 0
    d_model: int = 1024
    n_heads: int = 16
    latent_res: Optional[int] = None  # flux operates on latents
    cond_dim: int = 768              # text/conditioning embedding width (stub)
    n_classes: int = 1000            # DiT class conditioning
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def family(self) -> str:
        return "diffusion"

    @property
    def is_mmdit(self) -> bool:
        return self.n_double_blocks > 0


@dataclass(frozen=True)
class DetectorConfig:
    """MadEye approximation model: a light transformer backbone +
    anchor-free det heads.

    `backbone` "vit": `n_layers` blocks at `d_model` with `n_heads`
    heads over `patch` px patches. "swin": stages of `depths` blocks
    over `patch` px patches, `d_model` / `n_heads` / `d_ff` those of the
    first stage (each later stage doubles all three), attention in
    `window` x `window` windows; `n_layers` is sum(depths)."""
    name: str
    img_res: int
    patch: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    n_classes: int = 2               # {person, car}
    max_boxes: int = 32              # static box budget per frame
    fpn_dim: int = 128
    dtype: Any = jnp.float32
    remat: bool = False
    backbone: str = "vit"
    depths: tuple = ()               # swin: blocks per stage
    window: int = 7                  # swin: attention window side

    def __post_init__(self):
        # a JSON configuration gives lists; a jit-static config must hash
        object.__setattr__(self, "depths", tuple(self.depths))
        if self.backbone not in ("vit", "swin"):
            raise ValueError(f"unknown detector backbone "
                             f"{self.backbone!r}; known: vit, swin")
        if self.backbone == "swin" and sum(self.depths) != self.n_layers:
            raise ValueError(f"swin n_layers={self.n_layers} must be "
                             f"sum(depths)={sum(self.depths)}")

    @property
    def token_grid(self) -> int:
        """Side of the patch-token grid the backbone takes."""
        return self.img_res // self.patch

    @property
    def feature_grid(self) -> int:
        """Side of the map the neck and heads see: the token grid (ViT),
        or Swin's second-to-last stage, which the last is merged onto."""
        if self.backbone == "swin":
            return self.token_grid >> (len(self.depths) - 2)
        return self.token_grid

    @property
    def family(self) -> str:
        return "detector"


@dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell for an architecture family."""
    name: str
    kind: str         # train | prefill | decode | generate | serve
    seq_len: int = 0
    global_batch: int = 0
    img_res: int = 0
    steps: int = 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Any] = {}
_SMOKE: dict[str, Any] = {}


def register(cfg, smoke=None):
    _REGISTRY[cfg.name] = cfg
    if smoke is not None:
        _SMOKE[cfg.name] = smoke
    return cfg


def get_config(name: str):
    if name not in _REGISTRY:
        # import all config modules lazily on first miss
        import repro.configs  # noqa: F401  (triggers registration)
        from repro.configs import ALL_MODULES  # noqa: F401
    return _REGISTRY[name]


def get_smoke_config(name: str):
    if name not in _SMOKE:
        import repro.configs  # noqa: F401
    return _SMOKE[name]


def list_archs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)
