"""The chunked gated-delta-rule scan (``gymfx_tpu/ops/kda_chunk_scan.py``)
against the recurrence walked position by position (the reference's form,
``gymfx_tpu/reference/hybrid_decoder.py::delta_rule``), in float32: outputs
and the gradients of q, k, v, the log-decay and beta; windows that are and are
not a multiple of the chunk; keys that look alike (where powers of the
chunk's triangular matrix outgrow float32); the triangular inverse; the short
causal convolution against a shifted sum.  The scan's own backward rule
against JAX's derivative of the same forward, under ``jax.checkpoint`` inside
``lax.scan`` (how the trunk's blocks call it), and what it keeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gymfx_tpu.ops import kda_chunk_scan as ops
from gymfx_tpu.reference import hybrid_decoder as ref


def operands(batch, window, heads, width, seed, alike=0.0, decay=1.0):
    """Unit keys (``alike``: how much of a shared direction every key holds),
    scaled unit queries, log-decays down to the published bound -5."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    shape = (batch, window, heads, width)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    q = unit(normal(keys[0], shape)) * width ** -0.5
    k = unit(alike * normal(keys[5], (batch, 1, heads, width))
             + (1.0 - alike) * normal(keys[1], shape))
    v = normal(keys[2], shape)
    g = -5.0 * decay * jax.nn.sigmoid(3.0 * normal(keys[3], shape))
    beta = jax.nn.sigmoid(normal(keys[4], shape[:-1]) + 2.0 * alike)
    return tuple(x.astype(jnp.float32) for x in (q, k, v, g, beta))


def walked(*args):
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(*args, {})


@pytest.mark.parametrize("window, chunk", [(128, 64), (100, 64), (64, 16), (40, 16)])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(window, chunk):
    args = operands(2, window, 2, 32, seed=window)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, jnp.float32)
    out = ops.kda_chunk_scan(*args, chunk=chunk)
    want = walked(*args)
    assert out.shape == want.shape and out.dtype == jnp.float32
    np.testing.assert_allclose(out, want, atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(ops.kda_chunk_scan(*a, chunk=chunk) * weight),
                   argnums=(0, 1, 2, 3, 4))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(walked(*a) * weight), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, wanted):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("alike, decay", [(0.6, 0.02), (0.9, 0.002), (0.6, 1.0)])
def test_keys_that_look_alike_and_a_slow_decay_stay_the_recurrence(alike, decay):
    """k_t . k_i near 0.8 and beta near 0.9: the chunk's strictly triangular
    matrix has row sums in the tens and its powers leave float32 long before
    the 64th; the inverse is built from blocks of itself and stays exact."""
    args = operands(1, 128, 2, 32, seed=3, alike=alike, decay=decay)
    out, want = ops.kda_chunk_scan(*args), walked(*args)
    assert float(jnp.abs(want).max()) > 0.01
    np.testing.assert_allclose(out, want, atol=1e-4 * float(jnp.abs(want).max()))


def test_bfloat16_operands_keep_float32_decays_and_stay_near_the_recurrence():
    q, k, v, g, beta = operands(1, 128, 2, 32, seed=5, alike=0.3, decay=0.1)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    out = ops.kda_chunk_scan(*low, g, beta)
    want = walked(*[x.astype(jnp.float32) for x in low], g, beta)
    assert out.dtype == jnp.bfloat16
    error = jnp.linalg.norm(out.astype(jnp.float32) - want) / jnp.linalg.norm(want)
    assert float(error) < 0.02


NAMES = "q k v g beta".split()


def gradients(scan, args, weight, **kwargs):
    return jax.grad(lambda *a: jnp.sum(scan(*a, **kwargs).astype(jnp.float32) * weight),
                    argnums=(0, 1, 2, 3, 4))(*args)


def assert_gradients_close(got, wanted, tolerance):
    """Each of the five within ``tolerance`` of its wanted gradient's largest value."""
    for name, a, b in zip(NAMES, got, wanted):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(a, b, atol=tolerance * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("window, chunk", [(128, 64), (100, 64), (40, 16)])
def test_the_backward_rule_is_the_derivative_of_the_forward(batch, window, chunk):
    """The rule written by hand against what JAX derives from the undecorated
    forward, in float32: one function, two backward passes."""
    args = operands(batch, window, 2, 32, seed=window + batch)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[2].shape, jnp.float32)
    np.testing.assert_array_equal(ops.kda_chunk_scan(*args, chunk=chunk),
                                  ops.scan_forward(*args, chunk))
    got = gradients(ops.kda_chunk_scan, args, weight, chunk=chunk)
    wanted = gradients(ops.scan_forward, args, weight, chunk=chunk)
    assert_gradients_close(got, wanted, 2e-6)


@pytest.mark.parametrize("alike, decay", [(0.6, 0.02), (0.9, 0.002), (0.6, 1.0)])
def test_gradients_with_keys_that_look_alike_and_a_slow_decay(alike, decay):
    """Where the chunk's inverse holds entries in the thousands the rule's
    ``-T^T dT T^T`` still is the recurrence's gradient."""
    args = operands(1, 128, 2, 32, seed=3, alike=alike, decay=decay)
    weight = jax.random.normal(jax.random.PRNGKey(8), args[2].shape, jnp.float32)
    got = gradients(ops.kda_chunk_scan, args, weight)
    assert_gradients_close(got, gradients(walked, args, weight), 5e-5)


@pytest.mark.parametrize("alike, decay", [(0.0, 1.0), (0.3, 0.1)])
def test_bfloat16_gradients_stay_near_the_float32_recurrences(alike, decay):
    q, k, v, g, beta = operands(2, 128, 2, 32, seed=5, alike=alike, decay=decay)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    weight = jax.random.normal(jax.random.PRNGKey(9), v.shape, jnp.float32)
    got = gradients(ops.kda_chunk_scan, (*low, g, beta), weight)
    wanted = gradients(walked, (*[x.astype(jnp.float32) for x in low], g, beta), weight)
    for name, a, b, like in zip(NAMES, got, wanted, (*low, g, beta)):
        assert a.dtype == like.dtype and a.shape == like.shape, name
        error = jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)
        assert float(error) < 0.03, (name, float(error))


@pytest.mark.parametrize("window, chunk", [(64, 16), (40, 16)])
def test_the_rule_under_checkpoint_inside_a_scan_of_layers(window, chunk):
    """How the trunk calls it: ``nn.remat`` blocks under ``nn.scan``."""
    args = operands(2, window, 2, 32, seed=11)
    scales = jnp.asarray([1.0, 0.7, 1.3], jnp.float32)

    def trunk(scan, checkpointed):
        def loss(q, k, v, g, beta):
            def layer(x, scale):
                return x + scan(q * scale, k, x, g * scale, beta, chunk=chunk), None

            body = jax.checkpoint(layer) if checkpointed else layer
            return jnp.sum(jax.lax.scan(body, v, scales)[0] ** 2)

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    assert_gradients_close(trunk(ops.kda_chunk_scan, True), trunk(ops.scan_forward, False), 2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch, window, chunk", [(1, 128, 64), (3, 100, 64), (2, 40, 16)])
def test_the_forward_rule_keeps_the_inputs_and_at_most_the_chunk_states(
        dtype, batch, window, chunk):
    """What lies between the forward and the backward pass: no more bytes than
    the five inputs and a float32 state a head and chunk."""
    q, k, v, g, beta = operands(batch, window, 2, 32, seed=1)
    args = (*[x.astype(dtype) for x in (q, k, v)], g, beta)
    kept = jax.tree.leaves(jax.eval_shape(
        lambda *a: jax.vjp(lambda *b: ops.kda_chunk_scan(*b, chunk=chunk), *a)[1], *args))
    size = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    states = batch * -(-window // chunk) * 2 * 32 * 32 * 4
    assert sum(map(size, kept)) <= sum(map(size, args)) + states


@pytest.mark.parametrize("size", [16, 64])
def test_the_inverse_by_halves_is_the_inverse(size):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size), (3, size, size), jnp.float32), -1)
    got = ops.unit_lower_inverse(a)
    want = jnp.linalg.inv(jnp.eye(size) + a.astype(jnp.float64))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * float(jnp.abs(want).max()))


def test_the_causal_convolution_is_a_shifted_sum_and_sees_no_later_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6), jnp.float32)
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6), jnp.float32)
    want = np.zeros(x.shape, np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(taps[j]) * np.asarray(x[:, t - 3 + j])
    np.testing.assert_allclose(ops.causal_conv(x, taps), want, atol=1e-6)
    np.testing.assert_allclose(ref.short_conv(x, taps), want, atol=1e-6)
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(ops.causal_conv(later, taps)[:, :5],
                                  ops.causal_conv(x, taps)[:, :5])
