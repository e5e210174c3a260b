"""``train/common.picked_logp``: the trainers' one pick of a discrete
action's log-probability, a compare and a sum in place of a gather.

The reference is what it replaced, ``take_along_axis`` (which the float32
references under ``gymfx_tpu/reference`` and ``benchmarks/checks`` keep):
the same value bit for bit, the same gradient to a rounding, and where
the two differ in meaning (a ``-inf`` beside the chosen action) the
select is the finite one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gymfx_tpu.train.common import picked_logp

# (leading shape, actions): PPO / IMPALA rows, the portfolio trainer's
# (batch, instruments, 3), and more actions than any trainer has
SHAPES = {"N_3": ((64,), 3), "B_I_3": ((8, 5), 3), "N_7": ((64,), 7)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def gathered_logp(logp_all, action):
    return jnp.take_along_axis(logp_all, action[..., None], axis=-1)[..., 0]


def draw(shape_id, dtype, seed=0):
    lead, n = SHAPES[shape_id]
    k_logits, k_action, k_weight = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = (5.0 * jax.random.normal(k_logits, (*lead, n))).astype(dtype)
    action = jax.random.randint(k_action, lead, 0, n, dtype=jnp.int32)
    weight = jax.random.normal(k_weight, lead, dtype=jnp.float32)
    return logits, action, weight


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape_id", SHAPES)
def test_forward_is_the_gathers_value_bit_for_bit(shape_id, dtype):
    logits, action, _ = draw(shape_id, DTYPES[dtype])
    logp_all = jax.nn.log_softmax(logits)
    got = jax.jit(picked_logp)(logp_all, action)
    want = gathered_logp(logp_all, action)
    assert got.dtype == want.dtype == DTYPES[dtype]
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("shape_id", SHAPES)
def test_gradient_of_a_weighted_sum_is_the_gathers(shape_id):
    logits, action, weight = draw(shape_id, jnp.float32, seed=1)

    def weighted(pick):
        return lambda x: jnp.sum(weight * pick(jax.nn.log_softmax(x), action))

    got = jax.jit(jax.grad(weighted(picked_logp)))(logits)
    want = jax.jit(jax.grad(weighted(gathered_logp)))(logits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-6)


@pytest.mark.parametrize("chosen", [0, 2])
def test_minus_infinity_beside_the_chosen_action_stays_finite(chosen):
    # a masked action: its log-probability is -inf, and it is not the one
    # chosen.  A product with a one-hot would read 0 * -inf = NaN here.
    logp_all = jnp.log(jnp.asarray(
        [[0.25, 0.0, 0.75], [0.5, 0.0, 0.5]], jnp.float32))
    action = jnp.full((2,), chosen, jnp.int32)
    value, grad = jax.value_and_grad(
        lambda x: jnp.sum(picked_logp(x, action)))(logp_all)
    assert np.isfinite(float(value))
    assert not np.isnan(np.asarray(grad)).any()
    np.testing.assert_array_equal(
        np.asarray(grad), np.asarray(jax.nn.one_hot(action, 3)))


@pytest.mark.parametrize("how", ["jit", "vmap", "grad"])
@pytest.mark.parametrize("shape_id", SHAPES)
def test_result_has_the_actions_shape(shape_id, how):
    logits, action, weight = draw(shape_id, jnp.float32, seed=2)
    logp_all = jax.nn.log_softmax(logits)
    if how == "jit":
        out = jax.jit(picked_logp)(logp_all, action)
    elif how == "vmap":
        out = jax.vmap(picked_logp)(logp_all, action)
    else:
        # d/dw of sum(w * picked) is the picked log-probabilities
        out = jax.grad(
            lambda w: jnp.sum(w * picked_logp(logp_all, action)))(weight)
    assert out.shape == action.shape
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(gathered_logp(logp_all, action)))
