"""The plain references against the program's own modules, at a tiny size
in float32 on the CPU: the same weights and rows give the same loss and the
same gradient. (On the chip the whole comparison runs at the timed size in
every run; this guards the reference files themselves.)"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import harness
from benchmarks.reference import common as C


def nest(flat):
    out = {}
    for path, v in flat.items():
        d = out
        parts = path.split("/")
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


def program_loss(module, params, mstate, x, y):
    logits, _ = module.apply({"params": params, **mstate}, x, train=True,
                             mutable=list(mstate))
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def compare(module, ref, cfg, x, y, x_for_program=None):
    key = jax.random.PRNGKey(3)
    flat = ref.init_params(key, cfg)
    # zero scales would hide every branch behind them: make them count
    flat = {p: (v + 0.5 if p.endswith("BatchNorm_2/scale") else v)
            for p, v in flat.items()}
    xp = x if x_for_program is None else x_for_program
    variables = module.init({"params": key}, xp[:2], train=False)
    mstate = {k: v for k, v in variables.items() if k != "params"}
    params = nest(flat)
    assert jax.tree.structure(params) == jax.tree.structure(
        variables["params"])
    lp, gp = jax.value_and_grad(
        lambda p: program_loss(module, p, mstate, xp, y))(params)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(p, (x, y, None), cfg))(flat)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    gp_flat = {harness.path_of(k): v for k, v in
               jax.tree_util.tree_flatten_with_path(gp)[0]}
    for path, g in gr.items():
        # two float32 programs with different reduction orders (flax's batch
        # norm takes the variance as E[x^2] - E[x]^2): a wrong architecture
        # is off by its whole size, not by a percent
        got, want = np.asarray(gp_flat[path]), np.asarray(g)
        assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want) \
            + 1e-6, path


def test_vgg_reference_equals_the_programs_module():
    from gaussiank_sgd_tpu.models.vgg import VGG16
    stages = [8, 8, "M", 16, "M", 16, "M", "M", "M"]
    cfg = {"arch": {"input_shape": [32, 32, 3], "num_classes": 10,
                    "hidden": 512, "stages": stages}}
    ref = harness.load_reference({"reference": "vgg16_cifar10"})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=6), jnp.int32)
    compare(VGG16(num_classes=10, dtype=jnp.float32, cfg=tuple(stages),
                  dropout=0.0), ref, cfg, x, y)


def test_resnet_reference_equals_the_programs_module():
    from gaussiank_sgd_tpu.models.resnet import ResNet50
    from gaussiank_sgd_tpu.training.losses import IMAGENET_NORM, _prep_pixels
    cfg = {"arch": {"input_shape": [32, 32, 3], "num_classes": 10,
                    "stem_width": 64, "stage_sizes": [1, 2, 1, 1],
                    "stage_widths": [64, 128, 256, 512]}}
    ref = harness.load_reference({"reference": "resnet50_imagenet"})
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 256, size=(4, 32, 32, 3)), jnp.uint8)
    y = jnp.asarray(rng.integers(0, 10, size=4), jnp.int32)
    compare(ResNet50(num_classes=10, dtype=jnp.float32,
                     stage_sizes=(1, 2, 1, 1)), ref, cfg, x, y,
            x_for_program=_prep_pixels(x, IMAGENET_NORM))


def test_the_dropout_mask_is_the_programs():
    """The one piece of the program's plumbing the harness mirrors."""
    import flax.linen as nn
    from benchmarks.dropout import program_keep_mask

    class Outer(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(4)(x)
            return nn.Dropout(0.5, deterministic=False)(jnp.ones((3, 8)))

    seed, step, worker = 17, 2, 1
    state_key = jax.random.split(jax.random.PRNGKey(seed), 3)[2]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(state_key, step), 0), worker)
    m = Outer()
    variables = m.init({"params": key}, jnp.ones((3, 2)))
    got = m.apply(variables, jnp.ones((3, 2)), rngs={"dropout": key})
    want = program_keep_mask(seed, step, worker, (3, 8), 0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(np.unique(np.asarray(want))) <= {0.0, 2.0}


def test_follow_steps_is_momentum_sgd_with_error_feedback():
    """Two workers, a quadratic loss, a hand-worked step."""
    params = {"w": jnp.asarray([1.0, -2.0, 3.0, 0.5])}

    def loss(p, batch):                    # gradient = w - batch
        return 0.5 * jnp.sum(jnp.square(p["w"] - batch))

    shards = [[jnp.zeros(4), jnp.asarray([2.0, 0.0, 0.0, 0.0])]]
    masks = [[jnp.asarray([True, False, False, False]),
              jnp.asarray([False, True, False, False])]]
    r = C.follow_steps(loss, params, shards, masks, lrs=[0.1], momentum=0.9,
                       weight_decay=0.5)
    # worker 0's gradient (1,-2,3,.5) sends entry 0; worker 1's (-1,-2,3,.5)
    # sends entry 1: G = ((1) + 0, 0 + (-2), 0, 0) / 2
    want_m = np.array([0.5, -1.0, 0.0, 0.0]) + 0.5 * np.array(
        [1.0, -2.0, 3.0, 0.5])
    np.testing.assert_allclose(np.asarray(r["params"]),
                               np.array([1.0, -2.0, 3.0, 0.5]) - 0.1 * want_m,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r["first_grad"]),
                               [0.0, -2.0, 3.0, 0.5], rtol=1e-6)
