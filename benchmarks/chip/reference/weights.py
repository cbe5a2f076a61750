"""Random detector weights from a seed, in the program's params layout.

The layout is the detector's pytree (ViT backbone with stacked blocks,
FPN-lite neck, three head convs); the scales are the usual ones: convs
He-normal, linear layers LeCun-normal (both truncated at 2 sigma), the
CLS token and position table 0.02, norms at identity, biases zero.
Made on the device in one jitted call, in float32 as served.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp


def _tn(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def _conv(key, kh, kw, cin, cout):
    return {"w": _tn(key, (kh, kw, cin, cout), math.sqrt(2.0 / (kh * kw
                                                                 * cin))),
            "b": jnp.zeros((cout,), jnp.float32)}


def _linear(key, shape):
    return {"w": _tn(key, shape, math.sqrt(1.0 / shape[-2])),
            "b": jnp.zeros(shape[:-2] + shape[-1:], jnp.float32)}


def _norm(lead, d):
    return {"scale": jnp.ones(lead + (d,), jnp.float32),
            "bias": jnp.zeros(lead + (d,), jnp.float32)}


@partial(jax.jit, static_argnames=("sizes",))
def _init(sizes: str, seed):
    d = json.loads(sizes)
    dm, ff, fpn, nl = d["d_model"], d["d_ff"], d["fpn_dim"], d["n_layers"]
    g = d["img_res"] // d["patch"]
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    lead = (nl,)
    vit = {
        "patch_embed": _conv(next(ks), d["patch"], d["patch"], 3, dm),
        "cls_token": _tn(next(ks), (1, 1, dm), 0.02),
        "pos_embed": _tn(next(ks), (1, g * g + 1, dm), 0.02),
        "layers": {
            "norm1": _norm(lead, dm),
            "attn": {w: _linear(next(ks), lead + (dm, dm))
                     for w in ("wq", "wk", "wv", "wo")},
            "norm2": _norm(lead, dm),
            "mlp": {"up": _linear(next(ks), lead + (dm, ff)),
                    "down": _linear(next(ks), lead + (ff, dm))},
        },
        "final_norm": _norm((), dm),
        "head": _linear(next(ks), (dm, 2)),
    }
    return {
        "backbone": {
            "vit": vit,
            "neck": {"lateral": _conv(next(ks), 1, 1, dm, fpn),
                     "smooth": _conv(next(ks), 3, 3, fpn, fpn)},
        },
        "heads": {"cls": _conv(next(ks), 3, 3, fpn, d["n_classes"]),
                  "box": _conv(next(ks), 3, 3, fpn, 4),
                  "obj": _conv(next(ks), 3, 3, fpn, 1)},
    }


def init(sizes: dict, seed: int):
    """Detector params for the config's `detector` sizes."""
    return _init(json.dumps(sizes, sort_keys=True), jnp.int32(seed))
