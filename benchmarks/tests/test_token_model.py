"""A token model through the harness at a tiny size on the CPU (PR 27): the
program's `lstm` on its synthetic `ptb` with a plain reference of its loss,
written by `tiny_root.py` as files and entries only, a one-arm mix, driven
through `run._run`. It puts to the harness what a configuration that is no
image classifier will: integer batches [rows, positions] sliced by rows a
worker, a data set sized by other keys than `synthetic_examples`, a head
that is a vocabulary projection, and embedding rows that a batch never
names."""

import types

import numpy as np
import pytest

from benchmarks import check, harness
from test_harness_cpu import drive


@pytest.mark.parametrize("cell_name,workers", [("tiny_lm_solo1", 1),
                                               ("tiny_lm_solo4", 4)])
def test_a_token_model_runs_end_to_end(tiny_root, capsys, cell_name, workers):
    rc, result, out = drive(tiny_root, capsys, cell_name)
    assert rc == 0 and result["correct"] is True, out
    assert set(result["metrics"]) == {"examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert f"check residual_devices: {workers}" in out
    assert "check head_grad_rel_err:" in out and "FAILED" not in out
    assert result["check"]["head_grad_rel_err"]["value"] < 1e-5
    assert "sparse trainer built" in out and "dense trainer" not in out


def test_the_data_set_is_sized_by_the_configurations_own_keys(tiny_root,
                                                              tmp_path):
    """`trainer_argv` forces `synthetic_examples` on an image configuration
    only: the token model names its data set's keys itself, one of them
    by the worker."""
    import json
    lm = harness.load_cell("tiny_lm_solo4", root=tiny_root)
    argv = harness.trainer_argv(lm["config_data"], lm["mix"], 3, "sparse",
                                str(tmp_path), False)
    with open(argv[1]) as f:
        fields = json.load(f)
    assert fields["dataset_kwargs"] == {
        "vocab_size": 300, "bptt": 12, "synthetic_tokens_n": 4 * 4 * 73}
    assert fields["nworkers"] == 4 and fields["compressor"] == "auto"
    vgg = harness.load_cell("tiny_dp4", root=tiny_root)
    argv = harness.trainer_argv(vgg["config_data"], vgg["mix"], 3, "dense",
                                str(tmp_path), False)
    with open(argv[1]) as f:
        assert json.load(f)["dataset_kwargs"] == {"synthetic_examples": 64}
    # its reference is a file of the throw-away root, found by its name
    ref = harness.load_reference(lm["config_data"])
    assert ref.__file__.startswith(tiny_root)
    assert ref.param_shapes(lm["config_data"])["Embed_0/embedding"] == (300,
                                                                        24)


@pytest.fixture(scope="module")
def lm_readings(tiny_root):
    cell = harness.load_cell("tiny_lm_solo4", root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, weights = harness.build_arms(cell, 21, out_dir, False)
        harness.first_steps(arms["sparse"], cell["config_data"])
        harness.warm_up(arms["sparse"], cell["mix"])
        first = arms["sparse"].first
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
    return cell, first, weights


def test_rows_that_no_batch_names_are_never_sent(lm_readings):
    """The embedding's rows of the tokens that a worker's batch does not
    hold: that worker's gradient is exactly zero there, so its residual
    stays exactly zero, which the sent-mask must not read as sent; and a
    row that no worker's batch holds is neither sent nor moved but by the
    weight decay."""
    cell, first, weights = lm_readings
    config = cell["config_data"]
    like = first["params"]
    assert list(like) == list(weights)
    sizes = [int(v.size) for v in like.values()]
    at = dict(zip(like, np.cumsum([0] + sizes[:-1])))
    vocab, embed = weights["Embed_0/embedding"].shape
    rows = slice(at["Embed_0/embedding"],
                 at["Embed_0/embedding"] + vocab * embed)
    per = config["trainer"]["batch_size"]
    tokens = np.asarray(first["batches"][0][0])
    assert tokens.dtype == np.int32 and tokens.shape == (4 * per, 12)
    residual = first["residual1"][:, rows].reshape(4, vocab, embed)
    mask = first["masks"][0][:, rows].reshape(4, vocab, embed)
    for w in range(4):
        named = np.zeros(vocab, bool)
        named[np.unique(tokens[w * per:(w + 1) * per])] = True
        assert 0 < named.sum() < vocab
        assert not residual[w][~named].any()
        assert not mask[w][~named].any()
        assert residual[w][named].any()
    named = np.zeros(vocab, bool)
    named[np.unique(tokens)] = True
    wd = np.float32(config["trainer"]["weight_decay"])
    m1 = first["momentum1"][rows].reshape(vocab, embed)
    np.testing.assert_array_equal(
        m1[~named], (wd * weights["Embed_0/embedding"])[~named])
    # the check reads the same: nothing double counted or lost, the head a
    # vocabulary projection whose gradient the reference reproduces
    arm = types.SimpleNamespace(name="sparse", first=dict(first))
    mine = {"sparse": check.program_readings(arm, weights, config)}
    assert mine["sparse"]["exact"]["double_counted"] == 0
    assert not mine["sparse"]["sent_any1"][rows].reshape(
        vocab, embed)[~named].any()
    ref = check.reference_readings(
        config, cell["mix"], 21, {"sparse": first["batches"]},
        first["masks"], weights)
    grad = ref["sparse"]["first_grad"]["Embed_0/embedding"]
    assert not grad[~named].any() and grad[named].any()
    numbers = check.compare(mine, ref, config["head_leaf"])
    assert numbers["head_grad_rel_err"] < 1e-5
    assert check.lost_entries(mine["sparse"], ref["sparse"],
                              int(first["k"])) == 0


def test_a_vector_is_reordered_in_one_pass_or_not_at_all():
    like = {"b": np.zeros((2, 3)), "a": np.zeros((4,)), "c": np.zeros((1,))}
    flat = np.arange(11.0)
    same = check._reordered(flat, like, ["b", "a", "c"], ["b", "a", "c"])
    assert same is flat
    moved = check._reordered(flat, like, ["b", "a", "c"], ["a", "b", "c"])
    np.testing.assert_array_equal(
        moved, [6, 7, 8, 9, 0, 1, 2, 3, 4, 5, 10])
    back = check._reordered(moved, like, ["a", "b", "c"], ["b", "a", "c"])
    np.testing.assert_array_equal(back, flat)
