"""Compile the main path's kernels and the detector for a TPU v5e chip.

Nothing here runs: each test lowers and compiles at real width for one
chip of a described (not attached) v5e:2x2 topology, so what the chip's
compiler refuses — unaligned blocks, gathers Mosaic cannot lower, more
fast memory than a kernel may use, a program that does not fit HBM —
fails here at no chip time. A kernel is present when the compiled
program holds a `tpu_custom_call`. The benchmark cell's whole episode is
compiled once (module fixture) and read by several tests.

The topology is described only inside the module fixture (never at
import), and the persistent compilation cache is off around these
compiles: an executable for a described device is written but cannot be
read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

FLEET = 64                  # cameras on one chip at the published width
SHORTLIST_K = 18
N_OBJECTS = 22              # SceneSpec().max_objects
HBM_BYTES = 16 * 2**30
CAMERAS_A_CHIP = 8          # the sharded compile: its cost is the program's
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_neighbor_score_compiles_for_v5e(shape):
    from repro.kernels.neighbor_score.neighbor_score import (
        neighbor_score_batch,
    )

    lanes = 128
    strip, full = shape((FLEET, lanes)), shape((lanes, lanes))
    c = _compile(lambda *a: neighbor_score_batch(*a, interpret=False),
                 strip, strip, strip, full, full, full, full)
    assert "tpu_custom_call" in c.as_text()


def test_cell_rasterize_compiles_for_v5e(shape):
    from repro.kernels.cell_rasterize.cell_rasterize import (
        cell_rasterize_batch,
    )

    # ops.py's padding of the scene observation: 22 objects and 75
    # windows to 128 lanes, 4 student + 4 teacher pair channels
    strip = shape((FLEET, 128))
    c = _compile(
        lambda *a: cell_rasterize_batch(*a, n_pairs=8, n_moment=4,
                                        interpret=False),
        strip, strip, strip, strip, shape((FLEET, 8, 128)),
        shape((8, 8)), shape((8, 128)))
    assert "tpu_custom_call" in c.as_text()


def test_crop_patchify_compiles_for_v5e(shape):
    from repro.configs import get_config
    from repro.kernels.crop_patchify.crop_patchify import (
        crop_patchify_batch,
    )

    cfg = get_config("madeye-approx")
    gg, pp = (cfg.img_res // cfg.patch) ** 2, cfg.patch ** 2
    c = _compile(
        lambda *a: crop_patchify_batch(*a, interpret=False),
        shape((gg, pp), jnp.int32), shape((gg, pp), jnp.int32),
        shape((FLEET, SHORTLIST_K, 4, N_OBJECTS), jnp.int32),
        shape((FLEET, 3, N_OBJECTS)), shape((FLEET, 3, gg, pp)),
        shape((3, pp, cfg.d_model), jnp.bfloat16),
        shape((1, cfg.d_model)))
    assert "tpu_custom_call" in c.as_text()
    out = c.memory_analysis().output_size_in_bytes
    assert out >= FLEET * SHORTLIST_K * gg * cfg.d_model * 4


def test_crop_patchify_compiles_for_v5e_at_patch_4(shape):
    """Swin-T's patch 4 at width 96: eight 16-pixel patches a plane row
    ([392, 128] planes) against block-diagonal weights [3, 128, 768],
    whose [392, 768] rows are the [3136, 96] tokens."""
    from repro.configs import get_config
    from repro.kernels.crop_patchify.crop_patchify import (
        crop_patchify_batch,
    )
    from repro.kernels.crop_patchify.ops import pack_factor

    cfg = get_config("madeye-approx-swin-t")
    gg, pp = cfg.token_grid ** 2, cfg.patch ** 2
    r = pack_factor(gg, pp)
    assert (gg // r, r * pp, r * cfg.d_model) == (392, 128, 768)
    c = _compile(
        lambda *a: crop_patchify_batch(*a, interpret=False),
        shape((gg // r, r * pp), jnp.int32),
        shape((gg // r, r * pp), jnp.int32),
        shape((FLEET, SHORTLIST_K, 4, N_OBJECTS), jnp.int32),
        shape((FLEET, 3, N_OBJECTS)), shape((FLEET, 3, gg // r, r * pp)),
        shape((3, r * pp, r * cfg.d_model), jnp.bfloat16),
        shape((1, r * cfg.d_model)))
    assert "tpu_custom_call" in c.as_text()
    out = c.memory_analysis().output_size_in_bytes
    assert out >= FLEET * SHORTLIST_K * gg * cfg.d_model * 4


def test_full_detector_forward_compiles_for_v5e(shape):
    from repro.configs import get_config
    from repro.models.detector import detector_init
    from repro.serving.engine import detector_scores_tokens

    cfg = get_config("madeye-approx")
    params = jax.eval_shape(
        lambda k: detector_init(k, cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: shape(x.shape, x.dtype), params)
    gg = (cfg.img_res // cfg.patch) ** 2
    tokens = shape((FLEET * SHORTLIST_K, gg, cfg.d_model))
    c = detector_scores_tokens.lower(params, cfg, tokens).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


@pytest.fixture(scope="module")
def episode(shape):
    """The approx-k18-f15 benchmark cell's episode (madeye-approx at 64
    cameras, K = 18 of 75 windows), compiled for one chip: its optimized
    HLO text and its device bytes (arguments + outputs + temporaries -
    aliases)."""
    from benchmarks.chip import harness
    from repro.fleet import prepare_fleet_run
    from repro.fleet.runner import _episode

    config = harness.load_json("configs", "madeye-approx")
    traffic = harness.load_json("traffic", "k18-f15")
    assert config["cameras_per_chip"] == FLEET
    assert traffic["shortlist_k"] == SHORTLIST_K
    prep = prepare_fleet_run(harness.build_spec(
        config, traffic, 1, harness.draw_seeds(0, FLEET)))
    args = [jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)
            for tree in (prep.statics, prep.state, prep.provider)]
    c = _episode.lower(prep.cfg, prep.wl, *args, metrics=None).compile()
    return c.as_text(), harness.memory_bytes(c), config["detector"]


def test_episode_crops_through_the_kernel_for_v5e(episode):
    """Lowered for the TPU, the episode's crop-to-tokens stage is the
    Pallas kernel: a `tpu_custom_call` under the `crop_to_tokens`
    scope."""
    hlo, _, _ = episode
    scopes = [op for line in hlo.splitlines() if "tpu_custom_call" in line
              for op in OP_NAME.findall(line)]
    assert any("/crop_to_tokens/" in op for op in scopes), scopes


def test_episode_has_no_crop_pixel_buffer_for_v5e(episode):
    """No crop batch [n, res, res, 3] beyond one image a camera, and no
    flattened [pixels, 3] paint of the crops (3 channels padded to 128
    lanes is what the jnp reference costs on the chip)."""
    hlo, _, det = episode
    res = det["img_res"]
    crop = rf"\[(\d+),{res},{res},3\]"
    batches = [int(n) for n in re.findall(crop, hlo)]
    assert all(n <= FLEET for n in batches), sorted(set(batches))
    flat = [int(n) for n in re.findall(r"\[(\d+),3\]", hlo)]
    assert all(n < FLEET * res * res for n in flat), max(flat)


def test_episode_fits_two_gib_for_v5e(episode):
    """With the crops kept in VMEM the episode needs under 2 GiB of the
    chip's 16 (the jnp reference's pixel buffers took 7.6)."""
    _, nbytes, _ = episode
    assert nbytes < 2 * 2**30, nbytes


@pytest.fixture(scope="module")
def sharded_episode(topo):
    """The same cell's episode sharded over the four chips of the
    described v5e:2x2, as a `chips: 4` cell places it (the fleet axis of
    state and scene on the mesh `data` axis, the rest replicated), at
    CAMERAS_A_CHIP cameras a chip: its optimized HLO text."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks.chip import harness
    from repro.fleet import prepare_fleet_run
    from repro.fleet.runner import _episode, shard_fleet

    chips = len(topo.devices)
    mesh = Mesh(np.array(topo.devices).reshape(chips, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    config = dict(harness.load_json("configs", "madeye-approx"),
                  cameras_per_chip=CAMERAS_A_CHIP)
    traffic = harness.load_json("traffic", "k18-f15")
    prep = prepare_fleet_run(harness.build_spec(
        config, traffic, chips,
        harness.draw_seeds(0, CAMERAS_A_CHIP * chips)), mesh=mesh)

    def place(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    # the placement of the program's own shard hooks, with shapes put
    # where arrays would be: the described chips hold no memory
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "device_put", place)
        state = shard_fleet(prep.state, mesh)
        provider = prep.provider.shard(mesh)
    whole = NamedSharding(mesh, P())
    args = [jax.tree.map(lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
                         else place(x, whole), tree)
            for tree in (prep.statics, state, provider)]
    c = _episode.lower(prep.cfg, prep.wl, *args, metrics=None).compile()
    return c.as_text(), config["detector"]


def test_sharded_episode_runs_the_kernel_per_chip_for_v5e(sharded_episode):
    """Over four chips each chip runs the crop kernel on its own cameras
    (the compiler cannot partition a Pallas call): the custom call sits
    under `crop_to_tokens` and writes one chip's tokens."""
    hlo, det = sharded_episode
    gg = (det["img_res"] // det["patch"]) ** 2
    tokens = f"f32[{CAMERAS_A_CHIP},{SHORTLIST_K},{gg},{det['d_model']}]"
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line
             and any("/crop_to_tokens/" in op
                     for op in OP_NAME.findall(line))]
    assert calls
    assert all(tokens in line for line in calls), calls


@pytest.fixture(scope="module")
def swin_episode(shape):
    """The swin-t-k18-f15 cell's episode (Swin-T at 64 cameras, K = 18
    of 75 windows), compiled for one chip: its optimized HLO text and
    its device bytes."""
    from benchmarks.chip import harness
    from repro.fleet import prepare_fleet_run
    from repro.fleet.runner import _episode

    config = harness.load_json("configs", "madeye-approx-swin-t")
    traffic = harness.load_json("traffic", "k18-f15")
    prep = prepare_fleet_run(harness.build_spec(
        config, traffic, 1, harness.draw_seeds(0, FLEET)))
    args = [jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)
            for tree in (prep.statics, prep.state, prep.provider)]
    c = _episode.lower(prep.cfg, prep.wl, *args, metrics=None).compile()
    return c.as_text(), harness.memory_bytes(c)


def test_swin_episode_crops_through_the_packed_kernel_for_v5e(swin_episode):
    """The Swin cell's crops go through the Pallas kernel under
    `crop_to_tokens`, on planes of eight patches a row."""
    hlo, _ = swin_episode
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line
             and any("/crop_to_tokens/" in op
                     for op in OP_NAME.findall(line))]
    assert calls
    assert all("s32[392,128]" in line and "bf16[3,128,768]" in line
               for line in calls), calls


def test_swin_episode_fits_the_chip_for_v5e(swin_episode):
    """The backbone runs the [F*K] batch in slices sized from its shapes:
    the episode needs about a third of the chip (in one shot it would
    need 14.7 of 16 GiB)."""
    _, nbytes = swin_episode
    assert nbytes < 8 * 2**30, nbytes


def test_swin_episode_names_its_substages_for_v5e(swin_episode):
    """Window attention and patch merge ops of the episode sit inside
    `backbone` (the reducers of a reduce carry a shorter path, from
    inside the backbone's map over slices)."""
    from repro.obs.trace import STAGES

    hlo, _ = swin_episode
    for sub in ("window_attention", "patch_merge"):
        ops = [op.split("/") for op in OP_NAME.findall(hlo)
               if sub in op.split("/")]
        episode = [op for op in ops if op[0] == "jit(_episode)"]
        assert episode, sub
        assert all("backbone" in op[:op.index(sub)] for op in episode)
        assert not any(set(op) & (set(STAGES) - {"backbone"})
                       for op in ops), sub
