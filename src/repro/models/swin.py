"""Swin Transformer — windowed + shifted-window attention, patch merging
between stages. Pure JAX; stages and blocks are Python loops (hetero
dims, and odd blocks shift).

Two entries share the block: the Swin-B classifier (`swin_init` /
`swin_forward`, global pooling and a class head) and the approximation
detector's backbone (`swin_backbone_init` / `swin_features_tokens`,
patch tokens in, the last two stages out on one grid for the shared
neck). Each block's window attention and each patch merge carry a
`repro.obs.trace.substage` scope.

Layout: NHWC feature maps between stages; windows flattened for attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DetectorConfig, VisionConfig
from repro.models import attention as attn
from repro.models.layers import (
    Params,
    conv2d,
    conv_init,
    layernorm,
    layernorm_init,
    linear,
    linear_init,
    mlp,
    mlp_init,
    stack_init,
    trunc_normal,
)
from repro.obs.trace import substage


def _rel_position_index(window: int, table: int | None = None
                        ) -> np.ndarray:
    """[w^2, w^2] index into a (2t-1)^2 relative-bias table (t = `table`,
    default w): row (dy + t-1) * (2t-1) + (dx + t-1) for the offset
    (dy, dx) of key from query."""
    t = table or window
    coords = np.stack(np.meshgrid(np.arange(window), np.arange(window),
                                  indexing="ij"))  # [2, w, w]
    flat = coords.reshape(2, -1)                    # [2, w^2]
    rel = flat[:, :, None] - flat[:, None, :]       # [2, w^2, w^2]
    rel = rel.transpose(1, 2, 0) + (t - 1)
    return rel[..., 0] * (2 * t - 1) + rel[..., 1]


def _effective_window(map_size: int, preferred: int) -> int:
    """Largest window <= `preferred` that divides the feature map: the
    window itself where it divides, the whole map where the map is
    smaller (Swin's last stage). A 384 px Swin states its window, 12."""
    for w in range(min(preferred, map_size), 0, -1):
        if map_size % w == 0:
            return w
    return 1


def swin_block_init(key, dim: int, n_heads: int, window: int,
                    mlp_ratio: float = 4.0, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    n_bias = (2 * window - 1) ** 2                 # as published
    return {
        "norm1": layernorm_init(dim, dtype=dtype),
        "attn": {
            "wq": linear_init(jax.random.fold_in(k1, 0), dim, dim,
                              dtype=dtype),
            "wk": linear_init(jax.random.fold_in(k1, 1), dim, dim,
                              dtype=dtype),
            "wv": linear_init(jax.random.fold_in(k1, 2), dim, dim,
                              dtype=dtype),
            "wo": linear_init(jax.random.fold_in(k1, 3), dim, dim,
                              dtype=dtype),
        },
        "rel_bias": trunc_normal(k3, (n_bias, n_heads), dtype=dtype),
        "norm2": layernorm_init(dim, dtype=dtype),
        "mlp": mlp_init(k2, dim, int(dim * mlp_ratio), dtype=dtype),
    }


def swin_block(p: Params, x: jnp.ndarray, *, n_heads: int, window: int,
               odd: bool) -> jnp.ndarray:
    """x: [B, H, W, C] -> the same, through one block whose relative-bias
    table was sized for `window`. Attention runs in the largest windows
    up to `window` that tile the map; on an odd block they are shifted
    by half a window (cyclic roll plus the region mask), unless one
    window covers the whole map."""
    B, H, W, C = x.shape
    table, window = window, _effective_window(H, window)
    shift = window // 2 if odd and H > window else 0
    shortcut = x
    x = layernorm(p["norm1"], x)
    with substage("window_attention"):
        if shift > 0:
            x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
        wins = attn.window_partition(x, window)     # [B*nW, w^2, C]
        T = window * window
        rel_index = _rel_position_index(window, table).reshape(-1)
        rel_bias = p["rel_bias"][rel_index].reshape(T, T, -1)
        mask = (attn.shifted_window_mask(H, W, window, shift)
                if shift > 0 else None)
        wins = attn.window_attention(p["attn"], wins, n_heads=n_heads,
                                     rel_bias=rel_bias.transpose(2, 0, 1),
                                     mask=mask)
        x = attn.window_unpartition(wins, window, H, W)
        if shift > 0:
            x = jnp.roll(x, (shift, shift), axis=(1, 2))
    x = shortcut + x
    x = x + mlp(p["mlp"], layernorm(p["norm2"], x))
    return x


def patch_merge_init(key, dim: int, dtype=jnp.float32) -> Params:
    return {
        "norm": layernorm_init(4 * dim, dtype=dtype),
        "reduce": linear_init(key, 4 * dim, 2 * dim, bias=False, dtype=dtype),
    }


def patch_merge(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """[B, H, W, C] -> [B, H/2, W/2, 2C]: each 2 x 2 patch's pixels
    concatenated in the published order (row, col) = (0, 0), (1, 0),
    (0, 1), (1, 1), normed and reduced without bias."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // 2, 2, W // 2, 2, C)
    x = x.transpose(0, 1, 3, 4, 2, 5).reshape(B, H // 2, W // 2, 4 * C)
    return linear(p["reduce"], layernorm(p["norm"], x))


def swin_init(key, cfg: VisionConfig) -> Params:
    assert cfg.swin
    depths, dims = cfg.depths, cfg.dims
    keys = jax.random.split(key, len(depths) + 3)
    heads = [max(1, d // 32) for d in dims]
    stages = []
    for s, (depth, dim) in enumerate(zip(depths, dims)):
        bkeys = jax.random.split(keys[s], depth)
        blocks = [swin_block_init(bk, dim, heads[s], cfg.window,
                                  dtype=cfg.dtype) for bk in bkeys]
        stage = {"blocks": blocks}
        if s < len(depths) - 1:
            stage["merge"] = patch_merge_init(
                jax.random.fold_in(keys[s], 999), dim, dtype=cfg.dtype)
        stages.append(stage)
    return {
        "patch_embed": conv_init(keys[-3], cfg.patch, cfg.patch, 3, dims[0],
                                 dtype=cfg.dtype),
        "patch_norm": layernorm_init(dims[0], dtype=cfg.dtype),
        "stages": stages,
        "final_norm": layernorm_init(dims[-1], dtype=cfg.dtype),
        "head": linear_init(keys[-1], dims[-1], cfg.n_classes,
                            dtype=cfg.dtype),
    }


def swin_forward(params: Params, cfg: VisionConfig,
                 images: jnp.ndarray) -> jnp.ndarray:
    """images [B,H,W,3] -> logits [B, n_classes]."""
    heads = [max(1, d // 32) for d in cfg.dims]

    x = conv2d(params["patch_embed"], images.astype(cfg.dtype),
               stride=cfg.patch, padding="VALID")
    x = layernorm(params["patch_norm"], x)

    for s, stage in enumerate(params["stages"]):
        for b, bp in enumerate(stage["blocks"]):
            x = swin_block(bp, x, n_heads=heads[s], window=cfg.window,
                           odd=b % 2 == 1)
        if "merge" in stage:
            x = patch_merge(stage["merge"], x)

    x = layernorm(params["final_norm"], x)
    x = jnp.mean(x, axis=(1, 2))                    # global average pool
    return linear(params["head"], x)


# ---------------------------------------------------------------------------
# Detection backbone: patch tokens -> a two-stage feature map
# ---------------------------------------------------------------------------

# the largest activation one slice of the detector's batch may hold: the
# backbone runs a batch whose stage-1 MLP hidden would exceed it in as
# few equal slices as keep each under it
SLICE_BYTES = 2 ** 30


def swin_backbone_init(key, cfg: DetectorConfig) -> Params:
    """`params["backbone"]["swin"]` of the detector: the patch embedding
    and its norm, then per stage its blocks stacked on a leading axis,
    the patch merge after every stage but the last, and a norm on each
    of the two output stages."""
    ratio = cfg.d_ff / cfg.d_model
    n = len(cfg.depths)
    keys = jax.random.split(key, n + 1)
    stages = []
    for s, depth in enumerate(cfg.depths):
        dim, heads = cfg.d_model << s, cfg.n_heads << s
        stage = {"blocks": stack_init(
            keys[s], depth, lambda k: swin_block_init(
                k, dim, heads, cfg.window, ratio, dtype=cfg.dtype))}
        if s < n - 1:
            stage["merge"] = patch_merge_init(
                jax.random.fold_in(keys[s], 999), dim, dtype=cfg.dtype)
        if s >= n - 2:
            stage["out_norm"] = layernorm_init(dim, dtype=cfg.dtype)
        stages.append(stage)
    return {
        "patch_embed": conv_init(keys[-1], cfg.patch, cfg.patch, 3,
                                 cfg.d_model, dtype=cfg.dtype),
        "patch_norm": layernorm_init(cfg.d_model, dtype=cfg.dtype),
        "stages": stages,
    }


def swin_feature_width(cfg: DetectorConfig) -> int:
    """Channels of the map `swin_features_tokens` returns: the last two
    stages' widths side by side."""
    n = len(cfg.depths)
    return (cfg.d_model << (n - 2)) + (cfg.d_model << (n - 1))


def swin_features_tokens(params: Params, cfg: DetectorConfig,
                         tokens: jnp.ndarray) -> jnp.ndarray:
    """patch tokens [B, P, C] (the square token grid, row-major) ->
    feature map [B, g, g, D] on the grid of the second-to-last stage
    (`cfg.feature_grid`), D = `swin_feature_width`.

    Patch norm, then the stages; each of the last two is normed as it
    leaves (a detection backbone's outputs), and the last, half as
    fine, is upsampled x2 by nearest neighbour and set beside the one
    before. The neck's 1 x 1 lateral conv on that concatenation is
    FPN's top-down sum lateral(C3) + up(lateral(C4)): the conv is
    linear and commutes with nearest upsampling.

    A batch too large for `SLICE_BYTES` runs as a map over equal
    slices (`_n_slices`); each window's features depend on its own
    tokens alone."""
    B = tokens.shape[0]
    n = _n_slices(cfg, B)
    if n == 1:
        return _features(params, cfg, tokens)
    out = jax.lax.map(lambda t: _features(params, cfg, t),
                      tokens.reshape((n, B // n) + tokens.shape[1:]))
    return out.reshape((B,) + out.shape[2:])


def _n_slices(cfg: DetectorConfig, batch: int) -> int:
    """Fewest equal slices of `batch` windows whose stage-1 MLP hidden
    ([tokens, d_ff] a window, the largest activation) fits SLICE_BYTES."""
    per_window = (cfg.token_grid ** 2 * cfg.d_ff
                  * jnp.dtype(cfg.dtype).itemsize)
    n = 1
    while batch % n or (batch // n) * per_window > SLICE_BYTES:
        if n >= batch:
            return batch
        n += 1
    return n


def _features(params: Params, cfg: DetectorConfig,
              tokens: jnp.ndarray) -> jnp.ndarray:
    B, _, C = tokens.shape
    g = cfg.token_grid
    x = layernorm(params["patch_norm"], tokens).reshape(B, g, g, C)
    outs = []
    for s, stage in enumerate(params["stages"]):
        for b in range(cfg.depths[s]):
            bp = jax.tree.map(lambda a, b=b: a[b], stage["blocks"])
            x = swin_block(bp, x, n_heads=cfg.n_heads << s,
                           window=cfg.window, odd=b % 2 == 1)
        if "out_norm" in stage:
            outs.append(layernorm(stage["out_norm"], x))
        if "merge" in stage:
            with substage("patch_merge"):
                x = patch_merge(stage["merge"], x)
    fine, coarse = outs
    up = fine.shape[1] // coarse.shape[1]
    coarse = jnp.repeat(jnp.repeat(coarse, up, axis=1), up, axis=2)
    return jnp.concatenate([fine, coarse], axis=-1)


def swin_loss(params: Params, cfg: VisionConfig, images, labels):
    logits = swin_forward(params, cfg, images).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return jnp.mean(nll)
