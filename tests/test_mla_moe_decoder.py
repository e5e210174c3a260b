"""The ``mla_moe_decoder`` policy (gymfx_tpu/train/mla_moe_decoder.py) against
its plain reference (gymfx_tpu/reference/mla_moe_decoder.py) at a tiny size
on the CPU, float32, seeded weights: forward, PPO loss and gradients through
``PPOTrainer``'s loss, the router, the dropless dispatch, the shares of an
expert-parallel layer, rotary positions, causality, the train step's scopes
and counters, the Mosaic grouped product (interpreted, as everywhere on the
CPU) and a bfloat16 case."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.reference import mla_moe_decoder as ref
from gymfx_tpu.telemetry import scopes
from gymfx_tpu.train import mla_moe_decoder as mod
from gymfx_tpu.train.policies import make_policy
from tests.helpers import uptrend_df

ROOT = Path(__file__).resolve().parent.parent
TINY = dict(hidden_size=64, q_lora_rank=24, kv_lora_rank=16, num_attention_heads=4,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, intermediate_size=160,
            moe_intermediate_size=48, n_routed_experts=16, num_experts_per_tok=4,
            n_layers=3, experts_held=4, expert_offset=4)
REST = dict(first_k_dense_replace=1, n_shared_experts=1, routed_scaling_factor=1.8,
            norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=1e6)


def cfg_of(**over):
    return {**REST, **TINY, **over}


def policy_and_params(dtype=jnp.float32, seed=0, **over):
    policy = make_policy("mla_moe_decoder", dtype=dtype, **{**TINY, **over})
    tokens = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, 8, 5), jnp.float32)
    return policy, policy.init(jax.random.PRNGKey(seed), tokens[0]), tokens


def dims_of(**over):
    return mod.Dims(**{k: v for k, v in cfg_of(**over).items() if k in mod.Dims._fields})


def part_and_params(part, flat=False, **over):
    """One part of a block alone (a module class of the policy's file, or
    ``"dense"`` / ``"sparse"`` for a whole block), its parameters and a
    (2, 8, hidden) input, flattened to tokens for the expert layer."""
    dims = dims_of(**over)
    if part in ("dense", "sparse"):
        module = mod._Block(dims, part == "sparse", jnp.float32)
    else:
        module = part(dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, dims.hidden_size), jnp.float32)
    x = x.reshape(-1, dims.hidden_size) if flat else x
    return module, module.init(jax.random.PRNGKey(4), x), x, dims


# ---------------------------------------------------------------------------
# forward, loss, gradients
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("over", [{}, {"experts_held": 16, "expert_offset": 0},
                                  {"expert_offset": 12}],
                         ids=["share", "uncut", "last_share"])
def test_logits_and_values_are_the_references(over):
    policy, params, tokens = policy_and_params(**over)
    cfg = cfg_of(**over)
    logits, value = policy.apply(params, tokens)
    ref_logits, ref_value = ref.forward(ref.from_policy_params(params, cfg), tokens, cfg)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(value, ref_value, atol=2e-5)
    # a single window is the batch's row
    one_logits, one_value = policy.apply(params, tokens[1])
    np.testing.assert_allclose(one_logits, logits[1], atol=2e-6)
    assert one_value.shape == ()


def tiny_trainer(policy_dtype="float32", **policy_over):
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, timeframe="M1", num_envs=4, ppo_horizon=4, ppo_epochs=1,
                  ppo_minibatches=2, policy="mla_moe_decoder", policy_dtype=policy_dtype,
                  policy_kwargs={**TINY, **policy_over})
    env = Environment(config, dataset=MarketDataset(uptrend_df(120), config))
    return PPOTrainer(env, ppo_config_from(config))


def first_minibatch(trainer, seed=0):
    from gymfx_tpu.train.common import minibatch_plan

    state = trainer.init_state(seed)
    for _ in range(3):  # walk off the padded first window
        state, (traj, last_value) = jax.jit(trainer._rollout_phase)(state)
    advs, returns = trainer._gae(traj, last_value)
    fields = {"obs": traj["obs"], "action": traj["action"], "logp": traj["logp"],
              "adv": advs, "ret": returns, "pcarry": traj["pcarry"]}
    pcfg = trainer.pcfg
    _n, mb, take = minibatch_plan(fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
                                  horizon=pcfg.horizon, minibatches=pcfg.minibatches)
    return state.params, take(jnp.arange(mb)), traj


def test_loss_and_gradients_through_the_trainers_loss_are_the_references():
    trainer = tiny_trainer()
    params, batch, traj = first_minibatch(trainer)
    (loss, aux), grads = jax.value_and_grad(trainer._loss, has_aux=True)(params, batch)
    cfg = cfg_of()
    hyper = {"clip_eps": trainer.pcfg.clip_eps, "vf_coef": trainer.pcfg.vf_coef,
             "ent_coef": trainer.pcfg.ent_coef}
    ref_batch = {k: batch[k] for k in ("obs", "action", "logp", "adv", "ret")}
    ref_params = ref.from_policy_params(params, cfg)
    ref_loss, ref_grads = ref.ppo_loss_and_grads(ref_params, ref_batch, cfg, hyper)
    blocked_loss, blocked = ref.ppo_loss_and_grads(ref_params, ref_batch, cfg, hyper, block=3)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5)
    np.testing.assert_allclose(blocked_loss, ref_loss, atol=1e-6)
    np.testing.assert_allclose(ref.ppo_loss(ref_params, ref_batch, cfg, hyper), ref_loss,
                               atol=1e-6)
    got = ref.from_policy_params(grads, cfg)
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(ref_grads))
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(ref_grads),
                       jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, atol=2e-5 * scale)
        np.testing.assert_allclose(c, b, atol=2e-5 * scale)
    # the recorded log-probabilities and values are the reference's too
    obs = traj["obs"].reshape(-1, *traj["obs"].shape[2:])
    ref_logits, ref_value = ref.forward(ref_params, obs, cfg)
    ref_logp = jnp.take_along_axis(jax.nn.log_softmax(ref_logits),
                                   traj["action"].reshape(-1, 1), axis=1)[:, 0]
    np.testing.assert_allclose(traj["logp"].reshape(-1), ref_logp, atol=2e-5)
    np.testing.assert_allclose(traj["value"].reshape(-1), ref_value, atol=2e-5)
    assert 0.0 <= float(aux["moe_held_share"]) <= 1.0


@pytest.mark.parametrize("grad_scale", [1e-3, 10.0], ids=["unclipped", "clipped"])
def test_the_references_update_is_the_trainers_optimizer(grad_scale):
    """Three updates of ``PPOTrainer``'s optimizer (global-norm clip, Adam)
    against the reference's ``adam_update`` on the same gradients."""
    trainer = tiny_trainer()
    hyper = {"lr": trainer.pcfg.lr, "max_grad_norm": trainer.pcfg.max_grad_norm}
    params = {"a": jnp.linspace(-1.0, 1.0, 12).reshape(3, 4), "b": jnp.ones(5)}
    opt_state = trainer.optimizer.init(params)
    ref_params, moments = jax.tree.map(jnp.copy, params), ref.adam_init(params)
    for i in range(3):
        grads = jax.tree.map(lambda p, i=i: grad_scale * jnp.cos(p * (i + 1.0) + i), params)
        updates, opt_state = trainer.optimizer.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        ref_params, moments = ref.adam_update(ref_params, grads, moments, hyper)
    clipped = grad_scale * np.sqrt(17.0) > hyper["max_grad_norm"]
    assert clipped == (grad_scale > 1.0)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert moments[2] == 3


# ---------------------------------------------------------------------------
# the router and the dispatch
# ---------------------------------------------------------------------------
def test_the_bias_moves_the_choice_and_not_the_weight():
    dims = mod.Dims(n_routed_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.8)
    scores = jnp.array([[0.9, 0.8, 0.7, 0.6, 0.5, 0.4, 0.3, 0.1]], jnp.float32)
    idx, weights = mod.route(scores, jnp.zeros(8, jnp.float32), dims)
    assert sorted(idx[0].tolist()) == [0, 1]
    np.testing.assert_allclose(sorted(weights[0].tolist()),
                               [1.8 * 0.8 / 1.7, 1.8 * 0.9 / 1.7], rtol=1e-6)
    bias = jnp.zeros(8, jnp.float32).at[7].set(5.0)
    idx, weights = mod.route(scores, bias, dims)
    assert sorted(idx[0].tolist()) == [0, 7]           # the bias chose expert 7 ...
    by_expert = dict(zip(idx[0].tolist(), weights[0].tolist()))
    np.testing.assert_allclose(by_expert[7], 1.8 * 0.1 / 1.0, rtol=1e-6)  # ... at its score
    np.testing.assert_allclose(by_expert[0], 1.8 * 0.9 / 1.0, rtol=1e-6)
    np.testing.assert_allclose(sum(by_expert.values()), 1.8, rtol=1e-6)  # renormalised, scaled
    # the reference's router says the same
    p = {"router": jnp.eye(8, dtype=jnp.float32), "e_score_correction_bias": bias}
    logit = jnp.log(scores / (1 - scores))
    _s, ref_idx, ref_w = ref.router(p, logit, {"num_experts_per_tok": 2,
                                                "routed_scaling_factor": 1.8})
    assert sorted(ref_idx[0].tolist()) == [0, 7]
    np.testing.assert_allclose(sorted(ref_w[0].tolist()), sorted(weights[0].tolist()), rtol=1e-5)


def loads_of(scores, bias, dims):
    idx, _ = mod.route(scores, bias, dims)
    return np.bincount(np.asarray(idx).reshape(-1), minlength=dims.n_routed_experts)


@pytest.mark.parametrize("start", ["zeros", "drawn"])
def test_the_balanced_bias_evens_the_loads_of_the_batch_it_is_made_on(start):
    dims = mod.Dims(n_routed_experts=16, num_experts_per_tok=4)
    # scores that share what the tokens share: a few experts take most choices
    common = jax.random.normal(jax.random.PRNGKey(1), (16,))
    scores = jax.nn.sigmoid(common + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (512, 16)))
    first = (jnp.zeros(16) if start == "zeros"
             else 0.02 * jax.random.normal(jax.random.PRNGKey(3), (16,)))
    before = loads_of(scores, first, dims)
    assert before.max() > 2.5 * before.mean()
    bias = mod.balanced_choice_bias(scores, first, dims)
    after = loads_of(scores, bias, dims)
    assert after.sum() == before.sum() == 512 * 4
    assert after.max() <= 1.05 * after.mean() and after.min() >= 0.95 * after.mean()
    # it steers the choice only: the weights are still the chosen scores'
    idx, weights = mod.route(scores, bias, dims)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        weights, dims.routed_scaling_factor * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)


def test_identical_tokens_leave_the_balanced_bias_finite_and_near_its_start():
    dims = mod.Dims(n_routed_experts=16, num_experts_per_tok=4)
    scores = jnp.broadcast_to(jnp.linspace(0.2, 0.8, 16), (64, 16))
    bias = mod.balanced_choice_bias(scores, jnp.zeros(16), dims)
    assert np.isfinite(np.asarray(bias)).all() and float(jnp.abs(bias).max()) < 3.0


def test_the_layers_bias_is_balanced_on_the_batch_of_the_init_call_and_takes_no_gradient():
    layer, params, x, dims = part_and_params(mod.ExpertLayer, flat=True, expert_offset=0,
                                             experts_held=16)
    x = jnp.concatenate([x + 0.3 * jax.random.normal(jax.random.PRNGKey(i), x.shape)
                         for i in range(16)])          # 256 tokens that share a part
    params = layer.init(jax.random.PRNGKey(4), x)
    _out, _counters, idx = layer.apply(params, x)
    loads = np.bincount(np.asarray(idx).reshape(-1), minlength=16)
    assert loads.max() <= 1.1 * loads.mean()
    # another key: other weights, another bias, the same even load
    other = layer.init(jax.random.PRNGKey(5), x)
    assert not np.allclose(other["params"]["e_score_correction_bias"],
                           params["params"]["e_score_correction_bias"])
    grads = jax.grad(lambda p: jnp.sum(layer.apply(p, x)[0] ** 2))(params)
    assert not np.asarray(grads["params"]["e_score_correction_bias"]).any()


def test_random_episode_start_starts_the_first_episodes_at_offsets_of_their_own():
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, timeframe="M1", num_envs=8, ppo_horizon=4, ppo_epochs=1,
                  ppo_minibatches=2, policy="mla_moe_decoder", policy_dtype="float32",
                  policy_kwargs=dict(TINY), random_episode_start=True)
    env = Environment(config, dataset=MarketDataset(uptrend_df(400), config))
    trainer = PPOTrainer(env, ppo_config_from(config))
    state = trainer.init_state(3)
    bars = np.asarray(state.env_states.t)
    assert len(set(bars.tolist())) > 4 and bars.max() > 8
    # the windows hold bars, each env its own, and the policy was initialised on them
    obs = np.asarray(state.obs_vec)
    assert obs.shape[0] == 8 and not np.allclose(obs[0], obs[1])
    again = trainer.init_state(3)
    np.testing.assert_array_equal(np.asarray(again.env_states.t), bars)
    tree = jax.tree.map(np.asarray, state.params)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again.params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    step = jax.jit(trainer._train_step_impl, donate_argnums=0)
    state, metrics = step(state)                          # every leaf donated once
    assert np.isfinite(float(metrics["loss"]))
    # without the key every env starts at bar 0, as before
    assert not np.asarray(tiny_trainer().init_state(3).env_states.t).any()


def test_the_policy_is_initialised_on_a_walked_batch_that_holds_the_agents_states():
    trainer = tiny_trainer()              # 4 envs x 4 steps, 2 minibatches: steps 1 and 3 kept
    state = trainer.init_state(0)
    batch = np.asarray(trainer._first_batch(jax.random.PRNGKey(1), state.env_states))
    assert batch.shape == (8, *state.obs_vec.shape[1:])
    # scalar blocks are one value along a window: at the reset every env's are the
    # same (all flat), in the walked batch they differ from window to window
    reset = np.asarray(state.obs_vec)
    scalar = (np.ptp(reset, axis=1).max(axis=0) == 0) & (np.ptp(batch, axis=1).max(axis=0) == 0)
    assert scalar.any() and np.ptp(reset[:, 0, scalar], axis=0).max() == 0
    assert np.ptp(batch[:, 0, scalar], axis=0).max() > 0
    # the walk leaves the trainer's first states as they were, and init_state repeats
    again = trainer.init_state(0)
    np.testing.assert_array_equal(np.asarray(again.env_states.t), np.asarray(state.env_states.t))
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(again.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("offset", [4, 12])
def test_all_tokens_to_the_experts_held_here_loses_none(offset):
    """Every choice of every token on the four experts held: the worst case
    the buffer is sized for; nothing is dropped, nothing capped."""
    layer, params, x, dims = part_and_params(mod.ExpertLayer, flat=True, expert_offset=offset)
    bias = jnp.zeros(16, jnp.float32).at[offset:offset + 4].set(10.0)
    params = {"params": {**params["params"], "e_score_correction_bias": bias}}
    out, counters, idx = layer.apply(params, x)
    assert set(np.unique(idx).tolist()) == set(range(offset, offset + 4))
    assert float(counters[0]) == idx.size                 # all choices held here
    cfg = cfg_of(expert_offset=offset)
    y = ref.rms_norm(x, params["params"]["ffn_norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(params["params"], y, cfg)
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_the_short_buffer_and_the_worst_case_buffer_give_the_same():
    dims = mod.Dims(n_routed_experts=32, num_experts_per_tok=4, experts_held=4)
    # twice the expected rows against every choice, one tile more per expert in both
    assert mod.buffer_rows(64, dims, 8, worst=False) == 64 + 32
    assert mod.buffer_rows(64, dims, 8) == 256 + 32
    idx = jax.vmap(lambda k: jax.random.permutation(k, 32)[:4])(
        jax.random.split(jax.random.PRNGKey(0), 64))
    y = jax.random.normal(jax.random.PRNGKey(1), (64, 8), jnp.float32)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (4, 64), jnp.float32)
    held_here = (np.asarray(idx).T < 4)                          # (k, T)
    for align in (4, 8):
        short = mod.routing_plan(idx, dims, align, mod.buffer_rows(64, dims, align, worst=False))
        worst = mod.routing_plan(idx, dims, align)
        assert int(short.valid.sum()) == int(worst.valid.sum()) == held_here.sum()
        # the rows in use do not depend on the buffer: the tiles behind them are skipped
        np.testing.assert_array_equal(short.group_sizes, worst.group_sizes)
        assert int(short.group_sizes.sum()) <= short.valid.shape[0] < worst.valid.shape[0]
        for plan in (short, worst):
            np.testing.assert_array_equal(plan.held, held_here)
            rows = mod.dispatch_rows(y, plan)
            # every row holds its token; every token gets its held choices back, weighted
            np.testing.assert_array_equal(rows[plan.valid], y[plan.token[plan.valid]])
            np.testing.assert_allclose(
                mod.combine_rows(rows, weights, plan),
                y * jnp.sum(jnp.where(held_here, weights, 0), axis=0)[:, None], rtol=1e-6)
            # sorted by expert, every expert's rows from a multiple of the tile
            starts = np.cumsum(plan.group_sizes) - np.asarray(plan.group_sizes)
            assert (starts % align == 0).all()
            expert_of_row = np.asarray(idx).T.reshape(-1)[np.asarray(plan.choice)[plan.valid]]
            assert (np.diff(expert_of_row) >= 0).all()
        # the backward passes are gathers of the same map: against autodiff of plain takes
        def plain(y, weights, plan=worst):
            rows = jnp.where(plan.valid[:, None], y[plan.token], 0)
            picked = jnp.where(plan.held[..., None], rows[jnp.minimum(plan.dest, rows.shape[0] - 1)], 0)
            return jnp.sum(jnp.sum(picked * weights[..., None], axis=0) ** 2)
        def fused(y, weights, plan=worst):
            return jnp.sum(mod.combine_rows(mod.dispatch_rows(y, plan), weights, plan) ** 2)
        for got, want in zip(jax.grad(fused, (0, 1))(y, weights), jax.grad(plain, (0, 1))(y, weights)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def choices_for(routing, tokens, k, held, n, seed=0):
    """(T, k) expert choices, the experts held the first ``held`` of ``n``:
    ``even`` draws k distinct experts a token, token 0's then set ALL on experts
    held (as many as are held) and token 1's none; ``one_expert`` puts one
    choice of every token on expert 1 and the others on experts not held;
    ``none_held`` every choice on experts not held."""
    keys = jax.random.split(jax.random.PRNGKey(seed), tokens)
    if routing == "even":
        idx = jax.vmap(lambda key: jax.random.permutation(key, n)[:k])(keys)
        mostly_held = jnp.where(jnp.arange(k) < held, jnp.arange(k), held + jnp.arange(k))
        return idx.at[0].set(mostly_held % n).at[1].set((held + jnp.arange(k)) % n)
    away = jax.vmap(lambda key: held + jax.random.permutation(key, n - held)[:k])(keys)
    return away if routing == "none_held" else away.at[:, k // 2].set(1)


@pytest.mark.parametrize("tokens, k, held, n, hidden, buffer, routing", [
    (64, 4, 4, 32, 8, "short", "even"), (64, 4, 4, 32, 8, "worst", "even"),
    (64, 4, 4, 32, 8, "short", "one_expert"), (64, 4, 4, 32, 8, "worst", "one_expert"),
    (64, 4, 4, 32, 8, "short", "none_held"), (64, 4, 4, 32, 8, "worst", "none_held"),
    (128, 8, 8, 512, 16, "short", "even"), (128, 8, 8, 512, 16, "worst", "even"),
    (128, 8, 8, 512, 16, "short", "one_expert"), (128, 8, 8, 512, 16, "short", "none_held"),
    (96, 2, 2, 16, 8, "short", "even"), (32, 4, 16, 16, 8, "worst", "even"),
])
def test_rows_there_and_back_are_the_plain_takes_and_their_gradients(
        tokens, k, held, n, hidden, buffer, routing):
    """The way back (``_sum_of_choices``, ``combine_rows``) is the k-gather sum
    restated here, element for element; the gradients of rows there and back
    are autodiff's of plain takes: a weight's gradient as its row's own float32
    dot placed by one scatter, a padding row neither zeroed on the way there nor
    given a gradient on the way back."""
    dims = mod.Dims(n_routed_experts=n, num_experts_per_tok=k, experts_held=held)
    align = 8
    rows = mod.buffer_rows(tokens, dims, align, worst=buffer == "worst")
    idx = choices_for(routing, tokens, k, held, n)
    plan = mod.routing_plan(idx, dims, align, rows)
    held_here = np.asarray(idx).T < held
    np.testing.assert_array_equal(plan.held, held_here)
    assert int(plan.valid.sum()) == held_here.sum() <= rows      # the batch fits the buffer
    if routing == "even":                                       # all k held, and none
        assert held_here[:, 0].sum() == min(k, held)
        assert held_here[:, 1].sum() == (0 if held < n else k)      # the uncut layer holds all
    # the rows in use are the choices held, each once, sorted by expert
    at = np.asarray(plan.valid)
    np.testing.assert_array_equal(np.sort(np.asarray(plan.choice)[at]),
                                  np.flatnonzero(held_here.reshape(-1)))
    np.testing.assert_array_equal(np.asarray(plan.dest)[held_here],
                                  np.argsort(np.where(at, plan.choice, k * tokens))[:at.sum()])

    def plain(buffer_rows, weights=None):
        out = 0.0
        for j in range(k):
            part = jnp.where(plan.held[j][:, None],
                             buffer_rows[jnp.minimum(plan.dest[j], rows - 1)], 0)
            out = out + (part if weights is None else part * weights[j][:, None])
        return out

    for dtype in (jnp.float32, jnp.bfloat16):
        ys = jax.random.normal(jax.random.PRNGKey(3), (rows, hidden)).astype(dtype)
        ys = ys.at[~at].set(jnp.nan)            # what nothing may read: padding rows
        weights = jax.random.uniform(jax.random.PRNGKey(2), (k, tokens), jnp.float32, 0.1, 2.0)
        for w in (None, weights.astype(dtype)):
            got, want = mod._sum_of_choices(ys, plan, w), plain(ys, w)
            assert jnp.asarray(got).dtype == jnp.asarray(want).dtype
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
        np.testing.assert_array_equal(
            np.asarray(mod.combine_rows(ys, weights, plan), np.float32),
            np.asarray(plain(ys, weights.astype(dtype)), np.float32))

    # the gradients of rows there and back: autodiff of plain takes
    y = jax.random.normal(jax.random.PRNGKey(1), (tokens, hidden), jnp.float32)
    probe = jax.random.normal(jax.random.PRNGKey(4), (tokens, hidden), jnp.float32)
    there = mod.dispatch_rows(y, plan)
    np.testing.assert_array_equal(there[at], y[plan.token[at]])

    def plain_loss(y, weights):
        there = jnp.where(plan.valid[:, None], y[plan.token], 0)
        return jnp.sum(plain(there * there, weights) * probe)

    def fused_loss(y, weights):
        there = mod.dispatch_rows(y, plan)
        return jnp.sum(mod.combine_rows(there * there, weights, plan) * probe)

    for got, want in zip(jax.grad(fused_loss, (0, 1))(y, weights),
                         jax.grad(plain_loss, (0, 1))(y, weights)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(                 # a token none of whose choices is held
        jax.grad(fused_loss)(y, weights)[~held_here.any(axis=0)], 0)
    # a padding row gets no gradient on the way back, whatever the expert wrote there
    _, pull = jax.vjp(lambda ys, w: mod.combine_rows(ys, w, plan), ys.astype(jnp.float32), weights)
    g_ys, g_w = pull(probe)
    np.testing.assert_array_equal(g_ys[~at], 0)
    assert np.isfinite(np.asarray(g_w)).all() and (np.asarray(g_w)[~held_here] == 0).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their partial sums, the shared
    expert counted once, are the uncut reference's layer."""
    _layer, params, tokens, dims = part_and_params(
        mod.ExpertLayer, flat=True, experts_held=16, expert_offset=0)
    full = params["params"]
    y = ref.rms_norm(tokens, full["ffn_norm"], 1e-5)
    cfg = cfg_of(experts_held=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.expert_layer(full, y, cfg)
        shared = uncut - ref.expert_layer(full, y, cfg, shared=False)[0]
    total = jnp.zeros_like(uncut)
    for offset in range(0, 16, 4):
        share_dims = dims._replace(experts_held=4, expert_offset=offset)
        part = {k: (v[offset:offset + 4] if k.startswith("experts_") else v)
                for k, v in full.items()}
        out, _counters, _idx = mod.ExpertLayer(share_dims, jnp.float32).apply(
            {"params": part}, tokens)
        total = total + (out - shared)
    np.testing.assert_allclose(total + shared, uncut, atol=3e-5)


@pytest.mark.parametrize("routing", ["fits_the_short_buffer", "overflows_it"])
def test_the_layer_picks_its_buffer_by_the_batch_and_loses_no_token(routing):
    """The short buffer (twice the expected rows) is shorter than the worst
    case's, so the layer chooses under ``lax.cond``, forward and backward;
    either way output and gradients are the reference's.  128 tokens fit it;
    384 all of whose choices fall on the four experts held do not (three tiles
    of 128 an expert against 768 + 512 rows; at 128 tokens they would: an
    expert's 128 choices are one tile, which every expert has anyway), which
    the counter ``moe_short_buffer_share`` says."""
    dims = dims_of()
    tokens = 384 if routing == "overflows_it" else 128
    assert mod.buffer_rows(tokens, dims, 128, worst=False) < mod.buffer_rows(tokens, dims, 128)
    layer = mod.ExpertLayer(dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, dims.hidden_size), jnp.float32)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    if routing == "overflows_it":
        params = {**params, "e_score_correction_bias":
                  jnp.zeros(16, jnp.float32).at[4:8].set(10.0)}
    out, counters, _idx = layer.apply({"params": params}, x)
    assert (float(counters[0]) == 4 * tokens) == (routing == "overflows_it")
    assert float(counters[2]) == (0.0 if routing == "overflows_it" else 1.0)   # short buffer
    cfg = cfg_of()

    def want(p, x):
        with jax.default_matmul_precision("highest"):
            return ref.expert_layer(p, ref.rms_norm(x, p["ffn_norm"], 1e-5), cfg)[0]

    np.testing.assert_allclose(out, want(params, x), atol=3e-5)
    weight = jax.random.normal(jax.random.PRNGKey(6), out.shape, jnp.float32)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0] * weight), (0, 1))(params, x)
    ref_grads = jax.grad(lambda p, x: jnp.sum(want(p, x) * weight), (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, atol=2e-4 * max(1.0, float(jnp.max(jnp.abs(b)))))


# ---------------------------------------------------------------------------
# positions and causality
# ---------------------------------------------------------------------------
def test_rope_is_the_references_and_turns_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 4), jnp.float32)
    got = mod.rope_interleaved(x, 1e6)
    want = jnp.swapaxes(ref.rope(jnp.swapaxes(x, 1, 2), 1e6), 1, 2)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])                # position 0 stands
    pairs = lambda a: jnp.sum(a.reshape(*a.shape[:-1], 2, 2) ** 2, -1)  # noqa: E731
    np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5)      # a rotation of pairs


def test_positions_enter_by_the_rope_dims_only_and_one_rotary_key_serves_all_heads():
    layer, params, x, _dims = part_and_params(mod.LatentAttention)
    p = params["params"]
    # one rotary key: kv_a holds ONE block of rope dims, kv_b none
    assert p["kv_a"].shape == (64, 16 + 4) and p["kv_b"].shape == (16, 4 * (12 + 16))
    attend = lambda prm, v: layer.apply({"params": prm}, v)  # noqa: E731
    shuffled = x.at[:, :7].set(x[:, jnp.array([3, 0, 6, 1, 5, 2, 4])])
    moved = np.abs(attend(p, shuffled)[:, -1] - attend(p, x)[:, -1]).max()
    assert moved > 1e-3
    # without the rope columns of q the last position sees a SET of bars
    q_b = p["q_b"].reshape(24, 4, 16).at[:, :, 12:].set(0.0).reshape(24, 64)
    blind = {**p, "q_b": q_b}
    np.testing.assert_allclose(attend(blind, shuffled)[:, -1], attend(blind, x)[:, -1],
                               atol=1e-5)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_a_later_bar_leaves_earlier_positions_unchanged(kind):
    block, params, x, _ = part_and_params(kind)
    out, _ = block.apply(params, x)
    later, _ = block.apply(params, x.at[:, -1].add(1.0))
    np.testing.assert_allclose(later[:, :-1], out[:, :-1], atol=1e-6)
    assert np.abs(later[:, -1] - out[:, -1]).max() > 1e-3


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------
def test_two_train_steps_are_finite_with_the_expert_layers_scopes_and_counters():
    from gymfx_tpu.bench_util import compile_train_step

    trainer = tiny_trainer()
    state = trainer.init_state(0)
    step, _ = compile_train_step(trainer, state)
    for _ in range(2):
        state, metrics = step(state)
    assert all(np.isfinite(float(v)) for v in metrics.values())
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert 0.0 <= float(metrics["moe_short_buffer_share"]) <= 1.0
    paths = {scope.path for scope in scopes.last_step_scope_map().values()}
    for part in (scopes.ATTENTION, scopes.FFN) + scopes.MOE_SCOPES:
        assert scopes.join(scopes.ROLLOUT, scopes.POLICY_ACT, part) in paths
        assert scopes.join(scopes.UPDATE, scopes.LOSS, scopes.POLICY_FORWARD, part) in paths
    assert paths <= set(scopes.LAYERS) | set(scopes.GROUP_SCOPES)


def test_bfloat16_stays_within_its_bound_of_the_float32_reference():
    """bfloat16 products with float32 accumulation, three layers, hidden 64:
    logits and values within 0.06 of the reference on outputs of order 1
    (8 mantissa bits: 0.4 % a product, some tens of them in a row)."""
    policy, params, tokens = policy_and_params(dtype=jnp.bfloat16)
    cfg = cfg_of()
    logits, value = policy.apply(params, tokens)
    ref_logits, ref_value = ref.forward(ref.from_policy_params(params, cfg), tokens, cfg)
    assert logits.dtype == value.dtype == jnp.float32
    assert 1e-4 < np.abs(logits - ref_logits).max() < 0.06
    assert np.abs(value - ref_value).max() < 0.06


def test_the_lower_precision_reference_is_further_off_than_bfloat16():
    policy, params, tokens = policy_and_params()
    cfg = cfg_of()
    ref_params = ref.from_policy_params(params, cfg)
    exact = ref.forward(ref_params, tokens, cfg)[0]
    low = ref.forward(ref_params, tokens, {**cfg, "operand_dtype": "float8_e4m3fn"})[0]
    half = ref.forward(ref_params, tokens, {**cfg, "operand_dtype": "bfloat16"})[0]
    assert np.abs(low - exact).max() > 4 * np.abs(half - exact).max() > 0


# ---------------------------------------------------------------------------
# the Mosaic grouped product, interpreted
# ---------------------------------------------------------------------------
def test_the_grouped_product_kernel_is_ragged_dot_forward_and_backward():
    from gymfx_tpu.ops.grouped_matmul import grouped_matmul, tile_groups

    sizes, rows, tile = jnp.array([16, 8, 24]), 80, 8
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, 16), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 24), jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(2), (rows, 24), jnp.float32)
    group, used = tile_groups(sizes, rows, tile)
    assert group.tolist() == [0, 0, 1, 2, 2, 2, 2, 2, 2, 2] and used.tolist() == [6]
    inside = (jnp.arange(rows) < 48)[:, None]

    def kernel(lhs, rhs):
        out = grouped_matmul(lhs, rhs, group, used, tile_rows=tile, interpret=True)
        return jnp.where(inside, out, 0)              # rows past the tiles in use: unwritten

    def twin(lhs, rhs):
        return jax.lax.ragged_dot(lhs, rhs, sizes)

    np.testing.assert_allclose(kernel(lhs, rhs), twin(lhs, rhs), atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda *a: jnp.sum(twin(*a) * weight), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0][:48], want[0][:48], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)


# ---------------------------------------------------------------------------
# operations: the program's analytic count and the benchmark's
# ---------------------------------------------------------------------------
def test_the_analytic_flops_count_experts_by_their_active_share_as_the_benchmark_does():
    import sys

    from gymfx_tpu.telemetry.mfu import analytic_train_step_flops

    sys.path[:0] = [str(ROOT / "benchmarks")]
    try:
        import harness
        import run as bench_run
        rooflines = harness.load_module("rooflines", "mla_moe_decoder")
        cell = bench_run.load_cell("glm47flash_w256_train", False)
    finally:
        del sys.path[0]
    s = rooflines.sizes(cell)
    kwargs = cell["config"]["program"]["policy_kwargs"]
    policy = make_policy("mla_moe_decoder", dtype=jnp.bfloat16, **kwargs)
    tokens = jnp.zeros((s["window"], 11), jnp.float32)   # 7 z-scored columns + 4 scalars
    shapes = jax.eval_shape(policy.init, jax.random.PRNGKey(0), tokens)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 512_027_140
    analytic = analytic_train_step_flops(
        shapes, num_envs=s["envs"], horizon=s["horizon"], update_epochs=s["epochs"],
        tokens=s["window"], window=s["window"],
        d_model=s["num_attention_heads"] * s["v_head_dim"], n_layers=s["n_layers"],
        causal=True, expert_share=s["num_experts_per_tok"] / s["n_routed_experts"])
    required = rooflines.train_step_flops(cell)
    assert abs(analytic / required - 1.0) < 2e-3     # the heads and W_in, counted per token
    assert 120e12 < required < 126e12                # ISSUE 29: 123 TFLOP a train step
    # all experts held for every token would be this much more
    dense = analytic_train_step_flops(
        shapes, num_envs=s["envs"], horizon=s["horizon"], update_epochs=s["epochs"],
        tokens=s["window"], expert_share=1.0)
    assert dense > 1.5 * analytic


# ---------------------------------------------------------------------------
# the normal path beyond the trainer: the CLI, a checkpoint, serving
# ---------------------------------------------------------------------------
# the trunk with its mixers by the published ``layer_types`` (tests/test_conv_hybrid_decoder.py)
CONV_TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2, conv_L_cache=3,
                 intermediate_size=160, moe_intermediate_size=48, n_routed_experts=16,
                 num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0,
                 n_layers=4, experts_held=4, expert_offset=4,
                 layer_types=["conv", "conv", "conv", "full_attention"])


@pytest.mark.parametrize("kwargs, run, mixer, counter", [
    (TINY, "moe", "attn", "moe_load_max_over_mean"),
    (CONV_TINY, "moe_1", "conv", "short_conv_gate_rms"),
], ids=["latent_attention", "conv_hybrid"])
def test_cli_training_checkpoint_resume_and_one_served_decision(tmp_path, kwargs, run, mixer,
                                                                counter):
    """``--mode training`` with the policy's nested ``policy_kwargs`` as JSON
    on the command line -> checkpoint -> ``--resume_training`` -> a decision
    served by ``engine_from_config`` from that checkpoint (the engine calls such
    a policy on the bucket as it is: ``takes_batch``)."""
    from gymfx_tpu.app.main import main
    from gymfx_tpu.serve.engine import engine_from_config
    from gymfx_tpu.train.checkpoint import load_checkpoint, read_metadata

    ck = tmp_path / "ck"
    base = ["--mode", "training", "--input_data_file", "examples/data/eurusd_uptrend.csv",
            "--num_envs", "4", "--ppo_horizon", "4", "--ppo_minibatches", "2",
            "--window_size", "8", "--policy", "mla_moe_decoder",
            "--policy_kwargs", json.dumps(kwargs), "--train_total_steps", "32",
            "--checkpoint_dir", str(ck), "--quiet_mode"]
    first = main(base + ["--results_file", str(tmp_path / "r1.json")])
    assert np.isfinite(first["train_metrics"]["loss"])
    assert 0.0 <= first["train_metrics"]["moe_held_share"] <= 1.0
    assert first["train_metrics"][counter] > 0.0
    second = main(base + ["--resume_training", "true",
                          "--results_file", str(tmp_path / "r2.json")])
    assert np.isfinite(second["train_metrics"]["loss"])
    tree, step = load_checkpoint(str(ck))
    assert step == 64
    assert read_metadata(str(ck))["policy_kwargs"]["experts_held"] == 4
    block = tree["params"]["params"][run]
    assert mixer in block
    assert block["experts"]["experts_gate"].shape == (2, 4, 64, 48)

    config = dict(DEFAULT_VALUES)
    config.update(input_data_file="examples/data/eurusd_uptrend.csv", window_size=8,
                  timeframe="M1", checkpoint_dir=str(ck), serve_buckets=[1, 4])
    bundle = engine_from_config(config)     # policy and its widths from the checkpoint
    engine = bundle.engine
    result = engine.decide(np.asarray(bundle.encode(bundle.reset_obs), engine.obs_dtype))
    assert int(result.action) in (0, 1, 2) and np.isfinite(float(result.value))
    assert engine.late_compiles == 0
