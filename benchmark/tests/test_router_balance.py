"""The `nemotron_h` driver's balancing of the routers' selection biases, on
a router made by hand: sigmoid scores whose logits share an offset an expert
(what a common direction in the hidden states gives a router at its
initialisation), the top k of score + bias.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drivers.rllib_nemotron_h_token_trainer import BALANCE_STEPS, balanced

LAYERS, EXPERTS, K, TOKENS = 3, 32, 4, 4096


def router(seed):
    """`loads(biases, sample)` of a seeded router over seeded tokens."""
    rng = np.random.default_rng(seed)
    offsets = jnp.asarray(rng.normal(size=(LAYERS, 1, EXPERTS)), jnp.float32)

    def loads(biases, sample=0):
        noise = jax.random.normal(
            jax.random.PRNGKey(sample), (LAYERS, TOKENS, EXPERTS))
        scores = jax.nn.sigmoid(offsets + noise)
        _, chosen = jax.lax.top_k(scores + biases[:, None], K)
        return jax.vmap(lambda c: jnp.bincount(
            c.reshape(-1), length=EXPERTS))(chosen)
    return loads


def over_mean(load):
    load = np.asarray(load, np.float64)
    return load.max(-1) / load.mean(-1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_rule_balances_tokens_it_never_saw(seed):
    loads = router(seed)
    drawn = 0.02 * jax.random.normal(
        jax.random.PRNGKey(seed), (LAYERS, EXPERTS))
    assert over_mean(loads(drawn, 1)).min() > 2.0
    biases, along = jax.jit(lambda b: balanced(loads, b))(drawn)
    assert along.shape == (sum(n for _, n in BALANCE_STEPS), LAYERS)
    np.testing.assert_allclose(along[0], over_mean(loads(drawn)), rtol=1e-6)
    assert over_mean(loads(biases)).max() < 1.1
    # Other tokens: the offsets are the router's, not the sample's.
    assert over_mean(loads(biases, 1)).max() < 1.25
    # Every load counts each token's k choices, balanced or not.
    assert int(loads(biases, 1).sum()) == LAYERS * TOKENS * K


def test_a_balanced_router_stays_where_it_is():
    def loads(biases):
        return jnp.full((LAYERS, EXPERTS), TOKENS * K // EXPERTS)
    drawn = jnp.linspace(-0.1, 0.1, LAYERS * EXPERTS).reshape(
        LAYERS, EXPERTS)
    biases, along = balanced(loads, drawn)
    np.testing.assert_array_equal(biases, drawn)
    np.testing.assert_array_equal(along, 1.0)
