"""The plain reference against the program's model, small, float32, with
grouped-query attention: same weights, same ids, same logits and loss."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import arithmetic, models, reference

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = json.load(open(os.path.join(HERE, "data", "configs", "tiny-gqa.json")))


@pytest.fixture(scope="module")
def model():
    return models.build(TINY, 3)


def test_logits_and_loss_agree_with_the_program(model):
    import paddle_tpu as paddle

    ids = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], (2, 48)).astype(np.int32)
    weights = models.weights(model)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(weights, TINY, ids))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    rows = np.asarray(reference.logits(weights, TINY, ids,
                                       rows=[[5, 47], [0, 9]]))
    np.testing.assert_allclose(rows[0], want[0, [5, 47]], atol=1e-6)
    np.testing.assert_allclose(rows[1], want[1, [0, 9]], atol=1e-6)
    # padding on the right changes nothing before it
    padded = np.concatenate([ids, np.zeros((2, 7), np.int32)], axis=1)
    np.testing.assert_allclose(
        np.asarray(reference.logits(weights, TINY, padded))[:, :48], want,
        atol=1e-5)
    loss = float(np.asarray(model(paddle.to_tensor(ids),
                                  labels=paddle.to_tensor(ids))._data))
    want, grads = reference.loss_and_gradients(weights, TINY, ids, ids)
    assert want == pytest.approx(loss, abs=1e-5)
    assert set(grads) == set(reference.checked(TINY))


def test_gradients_are_those_of_the_whole_tensor(model):
    import jax
    import jax.numpy as jnp

    ids = np.random.default_rng(2).integers(
        0, TINY["vocab_size"], (2, 24)).astype(np.int32)
    weights = models.weights(model)
    _, grads = reference.loss_and_gradients(weights, TINY, ids, ids)

    def loss(head):
        z = reference.logits(dict(weights, **{"lm_head.weight": head}),
                             TINY, ids)
        return reference._mean_ce(z[:, :-1], jnp.asarray(ids)[:, 1:])

    whole = jax.grad(loss)(weights["lm_head.weight"])
    got = np.asarray(grads["lm_head.weight"])
    assert got.shape == whole.shape and np.abs(got).max() > 0
    np.testing.assert_allclose(got, np.asarray(whole), atol=1e-7, rtol=1e-4)
    # another batch runs the same program: the batch is no constant of it
    from benchmarks import probe
    builds = probe.Builds()
    other = np.random.default_rng(9).integers(
        0, TINY["vocab_size"], ids.shape).astype(np.int32)
    reference.loss_and_gradients(weights, TINY, other, other)
    assert builds.count == 0
    # only the rows of tokens that occur have a gradient in the embedding
    rows = np.abs(np.asarray(grads["llama.embed_tokens.weight"])).sum(1) > 0
    assert set(np.flatnonzero(rows)) == set(ids[:, :-1].ravel())


@pytest.mark.parametrize("update,share,size", [
    (lambda g: -3e-4 * np.sign(g), 1.0, 1.0),       # AdamW's first step
    (lambda g: np.zeros_like(g), 0.0, 0.0),         # no update
    (lambda g: 3e-4 * np.sign(g), 0.0, 1.0),        # ascent
    (lambda g: -3e-3 * np.sign(g), 1.0, 10.0),      # a wrong rate
])
def test_update_agreement(update, share, size):
    rng = np.random.default_rng(4)
    g = rng.normal(size=(8, 32)).astype(np.float32)
    g[:2] = 0.0                                     # rows without gradient
    w = rng.normal(size=g.shape).astype(np.float32)
    got = reference.update_agreement({"w": w}, {"w": w + update(g)},
                                     {"w": g}, 3e-4)["w"]
    assert got == pytest.approx((share, size), abs=1e-3)


def test_blocks_of_queries_change_nothing(model, monkeypatch):
    ids = np.random.default_rng(1).integers(
        0, TINY["vocab_size"], (1, 40)).astype(np.int32)
    weights = models.weights(model)
    whole = np.asarray(reference.logits(weights, TINY, ids))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    reference._layer.clear_cache()
    blocked = np.asarray(reference.logits(weights, TINY, ids))
    reference._layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, atol=1e-5)


def test_the_program_config_takes_every_size_from_the_file():
    cfg = models.program_config(TINY)
    for f in dataclasses.fields(cfg):
        if f.name in TINY:
            assert getattr(cfg, f.name) == TINY[f.name]
    assert cfg.dtype == "float32"


def test_parameter_counts_match_the_model(model):
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert arithmetic.total_params(TINY) == n


def test_flops_per_token_of_the_train_cell():
    cfg = json.load(open(os.path.join(HERE, "..", "configs",
                                      "mistral-7b.json")))
    assert arithmetic.matmul_params(cfg) == 570425344
    assert arithmetic.total_params(cfg) == 704663552
    assert arithmetic.train_flops_per_token(cfg, 4096) == pytest.approx(
        3.6239e9, rel=1e-4)


def test_an_unknown_chip_is_an_error():
    assert arithmetic.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        arithmetic.peaks("cpu")


def test_bf16_step():
    assert reference.bf16_step(1.0) == 2.0 ** -7
    assert reference.bf16_step(5.0) == 2.0 ** -5
