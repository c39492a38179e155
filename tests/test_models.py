"""Model zoo tests: init + forward shapes for every --dnn name the reference
accepts (SURVEY.md §2 C7/C8/C9), plus a BatchNorm-model integration with the
compressed train step (model_state threading)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.mesh import data_parallel_mesh, shard_batch
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step


def _init_and_forward(spec, batch_size=8, **call_kw):
    rng = jax.random.PRNGKey(0)
    if spec.task == "classify":
        x = jnp.zeros((batch_size,) + spec.input_shape, spec.input_dtype)
        variables = spec.module.init({"params": rng, "dropout": rng}, x,
                                     train=False)
        out = spec.module.apply(variables, x, train=False)
        return variables, out
    if spec.task == "lm":
        toks = jnp.zeros((batch_size,) + spec.input_shape, jnp.int32)
        variables = spec.module.init({"params": rng, "dropout": rng}, toks,
                                     train=False)
        return variables, spec.module.apply(variables, toks, train=False)
    if spec.task == "ctc":
        x = jnp.zeros((batch_size,) + spec.input_shape, jnp.float32)
        variables = spec.module.init({"params": rng, "dropout": rng}, x,
                                     train=False)
        return variables, spec.module.apply(variables, x, train=False)
    if spec.task == "seq2seq":
        src = jnp.ones((batch_size, 16), jnp.int32)
        tgt = jnp.ones((batch_size, 12), jnp.int32)
        variables = spec.module.init({"params": rng, "dropout": rng}, src,
                                     tgt, train=False)
        return variables, spec.module.apply(variables, src, tgt, train=False)
    raise AssertionError(spec.task)


def _param_count(variables):
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(variables["params"]))


@pytest.mark.parametrize("name", ["resnet20", "resnet32", "vgg16", "alexnet",
                                  "mnistnet"])
def test_cifar_family_shapes(name):
    spec = models.get_model(name)
    variables, out = _init_and_forward(spec)
    assert out.shape == (8, spec.num_classes)
    assert jnp.all(jnp.isfinite(out))


def test_resnet20_param_count():
    # He et al. report ~0.27M params for CIFAR ResNet-20 — option-A shortcuts
    spec = models.get_model("resnet20")
    variables, _ = _init_and_forward(spec)
    n = _param_count(variables)
    assert 0.25e6 < n < 0.30e6, n


def test_resnet50_shapes_and_size():
    spec = models.get_model("resnet50")
    variables, out = _init_and_forward(spec, batch_size=2)
    assert out.shape == (2, 1000)
    n = _param_count(variables)
    assert 24e6 < n < 27e6, n  # torchvision resnet50 has 25.6M


def test_lstm_lm_shapes():
    spec = models.get_model("lstm", vocab_size=1000, embed_dim=64,
                            hidden_dim=64)
    toks = jnp.ones((4, 35), jnp.int32)
    variables = spec.module.init({"params": jax.random.PRNGKey(0)}, toks,
                                 train=False)
    out = spec.module.apply(variables, toks, train=False)
    assert out.shape == (4, 35, 1000)


def test_lstman4_shapes():
    spec = models.get_model("lstman4", hidden=64, num_layers=1)
    x = jnp.ones((2, 161, 100), jnp.float32)
    variables = spec.module.init({"params": jax.random.PRNGKey(0)}, x,
                                 train=False)
    out = spec.module.apply(variables, x, train=False)
    assert out.ndim == 3 and out.shape[0] == 2 and out.shape[2] == 29
    assert out.shape[1] >= 10  # time downsampled by conv stride 2


def test_transformer_shapes():
    spec = models.get_model("transformer", vocab_size=100, dim=32, heads=4,
                            enc_layers=2, dec_layers=2, ffn=64, max_len=64)
    variables, out = _init_and_forward(spec, batch_size=4)
    assert out.shape == (4, 12, 100)


def test_unknown_model_raises():
    """An unknown name's error lists `NAMES`, and `TOKEN_MODELS` holds only
    names that `get_model` takes (an alias builds its model's spec)."""
    with pytest.raises(ValueError) as err:
        models.get_model("resnext9000")
    assert all(name in str(err.value) for name in models.NAMES)
    assert len(set(models.NAMES)) == len(models.NAMES)
    for name in models.TOKEN_MODELS:
        assert models.get_model(name).name in models.NAMES


# keywords that cut each family's widths to a size the CPU builds at once
TINY = {
    "transformer": dict(dim=32, heads=4, enc_layers=1, dec_layers=1, ffn=64,
                        max_len=64),
    "transformer_lm": dict(dim=32, heads=4, num_layers=1, ffn=64),
    "lstm": dict(embed_dim=32, hidden_dim=32, num_layers=1),
    "mellum2": dict(hidden_size=32, num_layers=1, num_heads=2,
                    num_kv_heads=1, head_dim=16, num_experts=4,
                    experts_per_token=2, expert_width=16,
                    layer_types=["sliding_attention"]),
    "joyai_flash": dict(hidden_size=32, num_layers=2, num_heads=2,
                        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
                        qk_rope_head_dim=8, v_head_dim=8, dense_width=32,
                        num_experts=4, experts_per_token=2, expert_width=16),
    "lfm2_moe": dict(hidden_size=32, num_layers=2, num_heads=2,
                     num_kv_heads=1, head_dim=16, dense_width=32,
                     num_dense_layers=1, num_experts=4, experts_per_token=2,
                     expert_width=16, layer_types=["conv", "full_attention"]),
    "afmoe": dict(hidden_size=32, num_layers=2, num_heads=2, num_kv_heads=1,
                  head_dim=16, dense_width=32, num_dense_layers=1,
                  num_experts=4, experts_per_token=2, expert_width=16,
                  layer_types=["sliding_attention", "full_attention"]),
    "qwen3_next": dict(hidden_size=32, num_layers=2, linear_num_key_heads=1,
                       linear_num_value_heads=2, linear_key_head_dim=8,
                       linear_value_head_dim=8, num_heads=2, num_kv_heads=1,
                       head_dim=16, num_experts=4, experts_per_token=2,
                       expert_width=16, shared_expert_width=16,
                       layer_types=["linear_attention", "full_attention"]),
    "nemotron_h": dict(hidden_size=32, pattern="ME*", mamba_num_heads=2,
                       mamba_head_dim=8, ssm_state_size=8, n_groups=1,
                       chunk_size=8, num_heads=2, num_kv_heads=1,
                       head_dim=16, num_experts=4, experts_per_token=2,
                       expert_width=16, shared_expert_width=16),
}


@pytest.mark.parametrize("name", models.NAMES)
def test_every_name_builds_its_spec(name):
    """One row of `get_model`'s tables a case: the name builds a `ModelSpec`
    under that name whose module initialises on one example's shape (tiny
    widths where the family has keywords for them), a token model takes
    `vocab_size` and hands it on as its head's rows, and a list of
    `layer_types` reaches the module as a tuple."""
    kw = dict(TINY.get(name, {}))
    if name in models.TOKEN_MODELS:
        kw["vocab_size"] = 48
        if name != "lstm":      # its 35 positions are fixed
            kw["seq_len"] = 16
    spec = models.get_model(name, **kw)
    assert spec.name == name
    assert (spec.num_classes == 48) == (name in models.TOKEN_MODELS)
    assert (spec.task in ("lm", "seq2seq")) == (name in models.TOKEN_MODELS)
    if "layer_types" in kw:
        assert spec.module.layer_types == tuple(kw["layer_types"])
    assert spec.counters == (name in ("mellum2", "joyai_flash", "lfm2_moe",
                                      "afmoe", "qwen3_next", "nemotron_h"))
    assert spec.mtp_lambda == (0.3 if name == "joyai_flash" else 0.0)
    x = jnp.zeros((2,) + spec.input_shape, spec.input_dtype)
    inputs = (x, x) if spec.task == "seq2seq" else (x,)
    shapes = jax.eval_shape(
        lambda *a: spec.module.init({"params": jax.random.PRNGKey(0)}, *a,
                                    train=False), *inputs)
    assert _param_count(shapes) > 0


def test_batchnorm_model_trains_with_compression():
    """End-to-end: a BN model (resnet20) through the sparse train step —
    model_state (batch_stats) must update and the loss must fall."""
    spec = models.get_model("resnet20")
    rng = jax.random.PRNGKey(0)
    # 16x16 crops: resnet20 is fully convolutional + global pool, and the
    # smaller spatial extent roughly halves CPU compile+step time (the test
    # checks BN-stat plumbing, not accuracy)
    x0 = jax.random.normal(rng, (32, 16, 16, 3))
    y0 = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, 10)
    variables = spec.module.init({"params": rng, "dropout": rng}, x0[:2],
                                 train=True)
    params, model_state = variables["params"], {
        k: v for k, v in variables.items() if k != "params"}

    def loss_fn(p, mstate, batch, drop_rng):
        x, y = batch
        logits, updated = spec.module.apply(
            {"params": p, **mstate}, x, train=True,
            mutable=["batch_stats"], rngs={"dropout": drop_rng})
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        acc = (logits.argmax(-1) == y).mean()
        return loss, (updated, {"acc": acc})

    mesh = data_parallel_mesh()
    comp = get_compressor("gaussian", density=0.01)
    plan = plan_for_params(params, 0.01)
    ts = build_dp_train_step(loss_fn, optax.sgd(0.05, momentum=0.9), comp,
                             plan, mesh)
    state = ts.init_state(params, jax.random.PRNGKey(7),
                          model_state=model_state)
    batch = shard_batch(mesh, (x0, y0))
    stats0 = jax.tree_util.tree_leaves(state.model_state)[0].copy()
    losses = []
    for _ in range(2):
        state, m = ts.dense_step(state, batch)
        losses.append(float(m.loss))
    for _ in range(10):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    stats1 = jax.tree_util.tree_leaves(state.model_state)[0]
    assert not np.allclose(np.asarray(stats0), np.asarray(stats1)), \
        "batch stats never updated"
    assert losses[-1] < losses[0], (losses[0], losses[-1])
