"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, assert output shapes + no NaNs (full configs run only via dryrun)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.configs.base import ShapeSpec
from repro.train import trainer

KEY = jax.random.PRNGKey(0)


def _smoke_shape(cfg):
    if cfg.family == "lm":
        return ShapeSpec("smoke", "train", seq_len=16, global_batch=2)
    return ShapeSpec("smoke", "train", img_res=cfg.img_res, global_batch=2)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_arch_train_step(arch):
    cfg = get_smoke_config(arch)
    shape = _smoke_shape(cfg)
    ts = trainer.make_train_step(cfg, lr=1e-3)
    params = ts.init_params(KEY)
    opt = ts.init_opt(params)

    # synthetic batch from the same specs the dry-run lowers
    specs = trainer.batch_specs(cfg, shape)
    batch = {}
    for name, sds in specs.items():
        k = jax.random.fold_in(KEY, abs(hash(name)) % 999)
        if sds.dtype == jnp.int32:
            hi = getattr(cfg, "vocab", getattr(cfg, "n_classes", 2))
            batch[name] = jax.random.randint(k, sds.shape, 0, hi)
        elif sds.dtype == jnp.bool_:
            batch[name] = jnp.ones(sds.shape, bool)
        else:
            batch[name] = jax.random.normal(k, sds.shape, sds.dtype) * 0.1

    params2, opt2, metrics = jax.jit(ts.step)(params, opt, batch, KEY)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch}: non-finite loss {loss}"
    assert float(metrics["grad_norm"]) > 0
    # params actually moved (note: bf16 dtype.kind is 'V', so compare all
    # floating leaves via issubdtype)
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2))
        if jnp.issubdtype(a.dtype, jnp.floating))
    assert moved, f"{arch}: optimizer step did not update params"


@pytest.mark.parametrize("arch", ["stablelm-3b", "stablelm-12b"])
def test_dense_lm_decode_matches_forward(arch):
    """Prefill+decode must agree with the full forward (exactness of the
    KV-cache serving path)."""
    from repro.models import kvcache as kvc
    from repro.models.transformer import lm_forward, lm_init

    cfg = get_smoke_config(arch)
    params = lm_init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab)

    full = lm_forward(params, cfg, toks)
    logits_p, cache = kvc.gqa_prefill(params, cfg, toks[:, :8], max_seq=16)
    np.testing.assert_allclose(np.asarray(logits_p, np.float32),
                               np.asarray(full[:, :8], np.float32),
                               atol=2e-2)
    for i in range(8, 12):
        logits_d, cache = kvc.gqa_decode_step(
            params, cfg, toks[:, i: i + 1], cache)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full[:, i], np.float32), atol=2e-2,
            err_msg=f"{arch} decode step {i} diverged from forward")


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "kimi-k2-1t-a32b"])
def test_moe_lm_decode_runs(arch):
    from repro.models import kvcache as kvc
    from repro.models.moe_lm import moe_lm_init

    cfg = get_smoke_config(arch)
    params = moe_lm_init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    if cfg.mla:
        logits, cache = kvc.mla_prefill(params, cfg, toks, max_seq=16)
        logits, cache = kvc.mla_decode_step(params, cfg, toks[:, :1], cache)
    else:
        logits, cache = kvc.moe_gqa_prefill(params, cfg, toks, max_seq=16)
        logits, cache = kvc.moe_gqa_decode_step(params, cfg, toks[:, :1],
                                                cache)
    assert logits.shape == (2, 1, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())
    assert int(cache.length) == 9


def test_mla_cache_is_compressed():
    """MLA's whole point: cache bytes/token ~ (lora + rope), far below
    GQA's 2 * Hkv * Dh."""
    from repro.models import kvcache as kvc
    cfg = get_smoke_config("deepseek-v3-671b")
    mla = kvc.init_mla_cache(cfg, 1, 8)
    mla_bytes = (mla.kv_latent.size + mla.k_rope.size) * 2
    gqa_equiv = 2 * cfg.n_layers * 8 * cfg.n_heads * cfg.resolved_head_dim * 2
    assert mla_bytes < gqa_equiv / 2


def test_detector_smoke():
    from repro.configs import get_smoke_config as gsc
    from repro.models import detector as det
    cfg = gsc("madeye-approx")
    params = det.detector_init(KEY, cfg)
    img = jax.random.normal(KEY, (2, cfg.img_res, cfg.img_res, 3))
    d = det.detector_forward(params, cfg, img)
    assert d.boxes.shape == (2, cfg.max_boxes, 4)
    assert d.scores.shape == (2, cfg.max_boxes)
    assert not bool(jnp.isnan(d.boxes).any())
    assert bool(jnp.all((d.scores >= 0) & (d.scores <= 1)))


def test_swin_detector_smoke():
    """The Swin-backed detector: image and token entries give the same
    detections, on the 4 x 4 head grid of the ViT smoke."""
    from repro.models import detector as det
    from repro.models import vit as vit_mod
    cfg = get_smoke_config("madeye-approx-swin-t")
    assert (cfg.backbone, cfg.token_grid, cfg.feature_grid) == ("swin", 16, 4)
    params = det.detector_init(KEY, cfg)
    assert det.backbone_name(params) == "swin"
    img = jax.random.uniform(KEY, (2, cfg.img_res, cfg.img_res, 3))
    d = det.detector_forward(params, cfg, img)
    tokens = vit_mod.vit_embed(params["backbone"]["swin"],
                               det._backbone_cfg(cfg), img)
    dt = det.detector_forward_tokens(params, cfg, tokens)
    assert d.scores.shape == (2, cfg.max_boxes)
    np.testing.assert_array_equal(np.asarray(d.scores),
                                  np.asarray(dt.scores))
    assert bool(jnp.all((d.scores >= 0) & (d.scores <= 1)))


def test_swin_b_smoke_initialises_and_runs():
    """The Swin-B classifier shares the detector's block: its smoke size
    still initialises and runs, with relative-bias tables of (2w-1)^2
    rows as published."""
    from repro.models import swin as swin_mod
    cfg = get_smoke_config("swin-b")
    params = swin_mod.swin_init(KEY, cfg)
    for stage in params["stages"]:
        for block in stage["blocks"]:
            assert block["rel_bias"].shape[0] == (2 * cfg.window - 1) ** 2
    img = jax.random.normal(KEY, (2, cfg.img_res, cfg.img_res, 3))
    logits = swin_mod.swin_forward(params, cfg, img)
    assert logits.shape == (2, cfg.n_classes)
    assert bool(jnp.isfinite(logits).all())


def test_diffusion_samplers_run():
    from repro.models import dit as dit_mod
    from repro.models import diffusion as diff
    cfg = get_smoke_config("dit-l2")
    params = dit_mod.dit_init(KEY, cfg)
    out = diff.dit_sample(params, cfg, KEY, batch=1, n_steps=2)
    assert out.shape[-1] == cfg.latent_channels
    assert not bool(jnp.isnan(out).any())

    from repro.models import mmdit as mm
    cfg2 = get_smoke_config("flux-dev")
    params2 = mm.mmdit_init(KEY, cfg2)
    out2 = diff.rf_sample(params2, cfg2, KEY, batch=1, n_steps=2)
    assert not bool(jnp.isnan(out2).any())


def test_vision_features_shape():
    from repro.models import vit as vit_mod
    cfg = get_smoke_config("vit-s16")
    params = vit_mod.vit_init(KEY, cfg)
    img = jax.random.normal(KEY, (2, cfg.img_res, cfg.img_res, 3))
    f = vit_mod.vit_features(params, cfg, img)
    g = cfg.img_res // cfg.patch
    assert f.shape == (2, g, g, cfg.d_model)
