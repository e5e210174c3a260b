"""transformer_ring policy: ring attention as a USED capability — the
same parameters produce numerically identical outputs whether the
observation window is on one device or sharded over a 'seq' mesh axis,
and the policy trains under PPO (SURVEY.md §5.7 mandate)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gymfx_tpu.parallel.mesh import make_mesh
from gymfx_tpu.parallel.ring_attention import full_attention, ring_attention
from gymfx_tpu.train.policies import (
    RingTransformerPolicy,
    make_policy,
    seq_sharded_forward,
    with_seq_sharding,
)
from tests.helpers import make_env, uptrend_df

N_DEV = len(jax.devices())


def _tokens(batch, window, dim, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, window, dim))


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device (CPU) mesh")
def test_seq_sharded_forward_matches_single_device():
    window = 8 * N_DEV
    policy = RingTransformerPolicy(window=window, d_model=32, n_heads=2,
                                   n_layers=2)
    tokens = _tokens(4, window, 12)
    params = policy.init(jax.random.PRNGKey(0), tokens[0])

    logits_ref, value_ref = jax.vmap(
        lambda t: policy.apply(params, t)
    )(tokens)

    mesh = make_mesh({"seq": N_DEV})
    logits_ring, value_ring = seq_sharded_forward(policy, params, tokens, mesh)

    np.testing.assert_allclose(
        np.asarray(logits_ring), np.asarray(logits_ref), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(value_ring), np.asarray(value_ref), atol=2e-5
    )


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device (CPU) mesh")
def test_batched_ring_attention_inner_matches_full():
    """ring_attention_inner with LEADING BATCH DIMS, called inside an
    explicit shard_map, against the batched full-attention oracle."""
    from jax.sharding import PartitionSpec as P

    from gymfx_tpu.parallel.ring_attention import ring_attention_inner

    window = 4 * N_DEV
    batch = 3
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (batch, window, 2, 8)) for kk in ks)
    mesh = make_mesh({"seq": N_DEV})
    spec = P(None, "seq", None, None)

    def f(qb, kb, vb):
        return ring_attention_inner(
            qb, kb, vb, axis="seq", n_shards=N_DEV, causal=True
        )

    from gymfx_tpu.parallel.mesh import shard_map

    out = shard_map(
        f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
    ref = full_attention(q, k, v, causal=True)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device (CPU) mesh")
def test_unbatched_ring_attention_matches_full():
    window = 4 * N_DEV
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (window, 2, 8)) for kk in ks)
    mesh = make_mesh({"seq": N_DEV})
    out = ring_attention(q, k, v, mesh=mesh, axis="seq", causal=True)
    ref = full_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_ring_policy_window_must_divide_shards():
    policy = RingTransformerPolicy(window=10)
    with pytest.raises(ValueError, match="divide"):
        with_seq_sharding(policy, "seq", 4)


def test_make_policy_knows_transformer_ring():
    p = make_policy("transformer_ring", window=16)
    assert isinstance(p, RingTransformerPolicy)


def test_impala_trains_with_transformer_ring_policy():
    from gymfx_tpu.train.impala import ImpalaConfig, ImpalaTrainer

    env = make_env(uptrend_df(120), window_size=8, num_envs=4)
    icfg = ImpalaConfig(n_envs=4, unroll=8, policy="transformer_ring")
    trainer = ImpalaTrainer(env, icfg)
    # token encoding (not flat) and the env window reached the policy
    assert trainer._is_transformer
    assert trainer.policy.window == 8
    state = trainer.init_state(0)
    state, metrics = trainer.train_step(state)
    assert np.isfinite(float(metrics["loss"]))


def test_ppo_trains_with_transformer_ring_policy():
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    env = make_env(uptrend_df(120), window_size=8, num_envs=4)
    config = dict(env.config, ppo_horizon=8, ppo_epochs=1, ppo_minibatches=2,
                  num_envs=4, policy="transformer_ring")
    trainer = PPOTrainer(env, ppo_config_from(config))
    state = trainer.init_state(0)
    state, metrics = trainer.train_step(state)
    assert np.isfinite(float(metrics["loss"]))
    state, metrics = trainer.train_step(state)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device (CPU) mesh")
def test_portfolio_ring_policy_seq_sharded_matches():
    """BASELINE config 5 combined: the PORTFOLIO ring policy with its
    window sharded over 'seq' matches its own single-device forward."""
    from gymfx_tpu.train.portfolio_ppo import PortfolioRingTransformerPolicy

    window = 8 * N_DEV
    policy = PortfolioRingTransformerPolicy(
        n_pairs=3, window=window, d_model=32, n_heads=2, n_layers=2
    )
    tokens = _tokens(4, window, 9, seed=5)
    params = policy.init(jax.random.PRNGKey(0), tokens[0])
    logits_ref, value_ref = jax.vmap(lambda t: policy.apply(params, t))(tokens)
    mesh = make_mesh({"seq": N_DEV})
    logits_ring, value_ring = seq_sharded_forward(policy, params, tokens, mesh)
    assert logits_ring.shape == logits_ref.shape == (4, 3, 3)
    np.testing.assert_allclose(
        np.asarray(logits_ring), np.asarray(logits_ref), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(value_ring), np.asarray(value_ref), atol=2e-5
    )


def test_portfolio_ppo_trains_with_transformer_ring(tmp_path):
    import pandas as pd

    from gymfx_tpu.core.portfolio import PortfolioEnvironment
    from gymfx_tpu.train.portfolio_ppo import (
        PortfolioPPOConfig,
        PortfolioPPOTrainer,
    )

    closes = 1.1 * (1.0 + 2e-4) ** np.arange(60)
    pd.DataFrame({
        "DATE_TIME": pd.date_range("2024-01-01", periods=60, freq="1min"),
        "OPEN": closes, "HIGH": closes, "LOW": closes, "CLOSE": closes,
        "VOLUME": 0.0,
    }).to_csv(tmp_path / "a.csv", index=False)
    env = PortfolioEnvironment({
        "portfolio_files": {"EUR_USD": str(tmp_path / "a.csv")},
        "window_size": 8,
    })
    pcfg = PortfolioPPOConfig(n_envs=4, horizon=8, epochs=1, minibatches=2,
                              policy="transformer_ring")
    tr = PortfolioPPOTrainer(env, pcfg)
    s = tr.init_state(0)
    s, m = tr.train_step(s)
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# PR 32: the q/k/v/o projections work on heads packed side by side on the
# last axis (policies.PackedHeadsDense); the PARAMETERS are what
# nn.DenseGeneral made them, so a checkpoint written before loads
# ---------------------------------------------------------------------------
def _ring_tree(encoder, heads, n_layers=2, d_model=128, n_heads=4, window=64,
               token_dim=7):
    """path -> shape of a ring policy's parameters as they have been since
    the encoder was written (all float32)."""
    hd = d_model // n_heads
    enc = {"Dense_0/kernel": (token_dim, d_model), "Dense_0/bias": (d_model,),
           "pos_embed": (window, d_model)}
    for i in range(2 * n_layers + 1):
        enc[f"LayerNorm_{i}/scale"] = enc[f"LayerNorm_{i}/bias"] = (d_model,)
    for layer in range(n_layers):
        for i in range(3):
            enc[f"DenseGeneral_{4 * layer + i}/kernel"] = (d_model, n_heads, hd)
            enc[f"DenseGeneral_{4 * layer + i}/bias"] = (n_heads, hd)
        enc[f"DenseGeneral_{4 * layer + 3}/kernel"] = (n_heads, hd, d_model)
        enc[f"DenseGeneral_{4 * layer + 3}/bias"] = (d_model,)
        enc[f"Dense_{2 * layer + 1}/kernel"] = (d_model, 4 * d_model)
        enc[f"Dense_{2 * layer + 1}/bias"] = (4 * d_model,)
        enc[f"Dense_{2 * layer + 2}/kernel"] = (4 * d_model, d_model)
        enc[f"Dense_{2 * layer + 2}/bias"] = (d_model,)
    tree = {f"params/{encoder}/{path}": shape for path, shape in enc.items()}
    for name, width in heads.items():
        tree[f"params/{name}/kernel"] = (d_model, width)
        tree[f"params/{name}/bias"] = (width,)
    return tree


@pytest.mark.parametrize("kind", ["single_pair", "portfolio"])
def test_ring_policy_parameter_tree_is_what_it_was(kind):
    from gymfx_tpu.train.portfolio_ppo import PortfolioRingTransformerPolicy

    if kind == "single_pair":
        policy = RingTransformerPolicy(window=64)
        want = _ring_tree("RingTransformerEncoder_0",
                          {"Dense_0": 3, "Dense_1": 1})
    else:
        policy = PortfolioRingTransformerPolicy(n_pairs=3, window=64)
        want = _ring_tree("RingTransformerEncoder_0",
                          {"Dense_0": 9, "Dense_1": 1})
    params = jax.eval_shape(
        policy.init, jax.random.PRNGKey(0), jnp.zeros((64, 7)))
    got = {
        "/".join(key.key for key in path): (leaf.shape, str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    assert got == {path: (shape, "float32") for path, shape in want.items()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_packed_heads_dense_is_dense_general_on_packed_heads(dtype):
    """Same parameters (names, shapes, the values drawn from a key) and the
    same numbers as the ``nn.DenseGeneral`` pair it stands for, into heads
    and out of them."""
    import flax.linen as nn

    from gymfx_tpu.train.policies import PackedHeadsDense, split_heads

    class General(nn.Module):
        @nn.compact
        def __call__(self, x):
            heads = nn.DenseGeneral((4, 32), dtype=dtype, name="into")(x)
            return heads, nn.DenseGeneral(
                128, axis=(-2, -1), dtype=dtype, name="out_of")(heads)

    class Packed(nn.Module):
        @nn.compact
        def __call__(self, x):
            heads = PackedHeadsDense(
                (128, 4, 32), contract=1, dtype=dtype, name="into")(x)
            return heads, PackedHeadsDense(
                (4, 32, 128), contract=2, dtype=dtype, name="out_of")(heads)

    x = jax.random.normal(jax.random.PRNGKey(2), (5, 16, 128))
    params = General().init(jax.random.PRNGKey(4), x)
    packed_params = Packed().init(jax.random.PRNGKey(4), x)
    assert jax.tree.structure(params) == jax.tree.structure(packed_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(packed_params)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a bias that is not zero, so that its packing is looked at too
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(6), p.shape),
        params)
    heads, out = General().apply(params, x)
    packed_heads, packed_out = Packed().apply(params, x)
    assert packed_heads.shape == (5, 16, 128) and packed_out.dtype == dtype
    # (bf16: the same products rounded the same way; float32: the CPU's
    # dot may order a 128-term sum differently for the two shapes)
    tol = 1e-5 if dtype == jnp.float32 else 0.0
    np.testing.assert_allclose(
        np.asarray(split_heads(packed_heads, 4), np.float32),
        np.asarray(heads, np.float32), atol=tol)
    np.testing.assert_allclose(
        np.asarray(packed_out, np.float32), np.asarray(out, np.float32),
        atol=tol)
