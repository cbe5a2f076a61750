"""Random detector weights from a seed, in the program's params layout.

The layout is the detector's pytree: the backbone's entries of
`params["backbone"]` (made by the module the configuration names,
`backbones/<name>.py`; see `backbones/vit.py`), an FPN-lite neck beside
them whose 1x1 lateral conv takes the backbone's feature width, and
three head convs. One stream of N_KEYS keys is split from the seed:
the backbone draws first (at most N_KEYS - 5; one that needs more
splits them from one of its own), then the neck and heads. The scales
are the usual ones: convs He-normal, linear layers LeCun-normal (both
truncated at 2 sigma), norms at identity, biases zero; a backbone may
add its own (ViT: CLS token and position table 0.02). Made on the
device in one jitted call, in float32 as served.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.chip import harness

N_KEYS = 16     # split from the seed; the neck and heads take 5


def trunc_normal(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def conv(key, kh, kw, cin, cout):
    return {"w": trunc_normal(key, (kh, kw, cin, cout),
                              math.sqrt(2.0 / (kh * kw * cin))),
            "b": jnp.zeros((cout,), jnp.float32)}


def linear(key, shape):
    return {"w": trunc_normal(key, shape, math.sqrt(1.0 / shape[-2])),
            "b": jnp.zeros(shape[:-2] + shape[-1:], jnp.float32)}


def norm(lead, d):
    return {"scale": jnp.ones(lead + (d,), jnp.float32),
            "bias": jnp.zeros(lead + (d,), jnp.float32)}


@partial(jax.jit, static_argnames=("sizes", "backbone"))
def _init(sizes: str, backbone: str | None, seed):
    d = json.loads(sizes)
    bb = harness.load_backbone(backbone)
    fpn = d["fpn_dim"]
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), N_KEYS))
    entries = bb.init(ks, d)
    width = bb.feature_shape(d)[-1]
    return {
        "backbone": {
            **entries,
            "neck": {"lateral": conv(next(ks), 1, 1, width, fpn),
                     "smooth": conv(next(ks), 3, 3, fpn, fpn)},
        },
        "heads": {"cls": conv(next(ks), 3, 3, fpn, d["n_classes"]),
                  "box": conv(next(ks), 3, 3, fpn, 4),
                  "obj": conv(next(ks), 3, 3, fpn, 1)},
    }


def init(config: dict, seed: int):
    """Detector params for a configuration: its `detector` sizes and the
    backbone its `backbone` key names."""
    return _init(json.dumps(config["detector"], sort_keys=True),
                 config.get("backbone"), jnp.int32(seed))
