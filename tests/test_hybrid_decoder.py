"""The hybrid decoder trunk (``gymfx_tpu/train/mla_moe_decoder.py`` with
``layer_group_size`` > 0: Kimi Delta Attention in five layers of six, latent
attention without the low-rank query path and with a head-wise gate in the
sixth, experts chosen by groups) against its plain reference
(``gymfx_tpu/reference/hybrid_decoder.py``: the recurrence position by position,
a dense loop over the experts held) -- and the all-latent trunk of the accepted
configuration pinned: its parameter tree, its counters, its layers' names."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.reference import hybrid_decoder as ref
from gymfx_tpu.train import mla_moe_decoder as mod
from gymfx_tpu.train.policies import make_policy
from tests.helpers import uptrend_df

ROOT = Path(__file__).resolve().parent.parent
# the published block at tiny widths: one period of six layers (five KDA, then
# MLA), the first dense; 16 experts in 4 groups of which 2 stay, top-4, 4 held
TINY = dict(hidden_size=64, q_lora_rank=None, kv_lora_rank=16, num_attention_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
            moe_intermediate_size=48, n_routed_experts=16, num_experts_per_tok=4, n_group=4,
            topk_group=2, routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=6e6,
            first_k_dense_replace=1, n_layers=6, experts_held=4, expert_offset=4,
            layer_group_size=6, attn_output_gate=True, kda_head_dim=16, kda_chunk=16)


def policy_and_params(dtype=jnp.float32, seed=0, window=40, **over):
    """The policy, parameters initialised on ONE batch and tokens of ANOTHER
    (the choice bias is balanced on the first: its tokens sit on the experts'
    thresholds, where a rounding error flips a choice)."""
    policy = make_policy("mla_moe_decoder", dtype=dtype, **{**TINY, **over})
    first = jax.random.normal(jax.random.PRNGKey(seed + 2), (3, window, 5), jnp.float32)
    tokens = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, window, 5), jnp.float32)
    return policy, policy.init(jax.random.PRNGKey(seed), first), tokens


def test_the_layers_kinds_follow_the_index_and_runs_of_one_kind_are_one_scan():
    assert mod.layer_runs(mod.layer_kinds(5), 1) == [
        ("dense_0", 0, 1, False, mod.LATENT), ("moe", 1, 4, True, mod.LATENT)]
    assert mod.layer_runs(mod.layer_kinds(6, 6), 1) == [
        ("dense_0", 0, 1, False, mod.LINEAR), ("moe_1", 1, 4, True, mod.LINEAR),
        ("moe_5", 5, 1, True, mod.LATENT)]
    assert [run[1:] for run in mod.layer_runs(mod.layer_kinds(12, 6), 2)] == ref.layer_runs(
        dict(n_layers=12, first_k_dense_replace=2, layer_group_size=6))
    _policy, params, _ = policy_and_params()
    tree = params["params"]
    assert set(tree["dense_0"]) == {"kda", "ffn"} and set(tree["moe_1"]) == {"kda", "experts"}
    assert set(tree["moe_5"]) == {"attn", "experts"}
    assert tree["moe_1"]["kda"]["q"].shape == (4, 64, 64)          # four layers, one scan
    assert tree["moe_1"]["kda"]["q_conv"].shape == (4, 4, 64)
    assert set(tree["moe_5"]["attn"]) == {"attn_norm", "q", "kv_a", "kv_a_norm", "kv_b",
                                          "head_gate", "o"}      # no q_a / q_b


@pytest.mark.parametrize("over", [{}, {"experts_held": 16, "expert_offset": 0},
                                  {"n_group": 1, "topk_group": 1}],
                         ids=["share", "all_held", "no_groups"])
def test_logits_values_and_choices_are_the_references(over):
    policy, params, tokens = policy_and_params(**over)
    cfg = {**TINY, **over}
    logits, value, chosen = policy.apply(params, tokens, routing=True)
    want_logits, want_value, want_chosen = ref.forward(
        ref.from_policy_params(params, cfg), tokens, cfg, with_routing=True)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(value, want_value, atol=2e-5)
    assert chosen.shape == want_chosen.shape == (5, 3 * 40, 4)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want_chosen, -1))


def test_the_counters_hold_the_linear_layers_mean_log_decay():
    policy, params, tokens = policy_and_params()
    assert policy.COUNTERS == ("moe_held_share", "moe_load_max_over_mean", "moe_short_buffer_share",
                               "kda_log_decay_mean")
    _, _, counted = policy.apply(params, tokens, counters=True)
    assert set(counted) == set(policy.COUNTERS)
    assert -5.0 < float(counted["kda_log_decay_mean"]) < 0.0
    glm = make_policy("mla_moe_decoder", **{**TINY, "layer_group_size": 0, "q_lora_rank": 24})
    assert glm.COUNTERS == ("moe_held_share", "moe_load_max_over_mean", "moe_short_buffer_share")


def tiny_trainer(policy_dtype="float32", **policy_over):
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(window_size=24, timeframe="M1", num_envs=4, ppo_horizon=4, ppo_epochs=1,
                  ppo_minibatches=2, policy="mla_moe_decoder", policy_dtype=policy_dtype,
                  random_episode_start=True, policy_kwargs={**TINY, **policy_over})
    env = Environment(config, dataset=MarketDataset(uptrend_df(200), config))
    return PPOTrainer(env, ppo_config_from(config))


def test_loss_and_gradients_through_the_trainers_loss_are_the_references():
    from gymfx_tpu.train.common import minibatch_plan

    trainer = tiny_trainer()
    state = trainer.init_state(0)
    state, (traj, last_value) = jax.jit(trainer._rollout_phase)(state)
    advs, returns = trainer._gae(traj, last_value)
    fields = {"obs": traj["obs"], "action": traj["action"], "logp": traj["logp"],
              "adv": advs, "ret": returns, "pcarry": traj["pcarry"]}
    pcfg = trainer.pcfg
    _n, mb, take = minibatch_plan(fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
                                  horizon=pcfg.horizon, minibatches=pcfg.minibatches)
    batch = take(jnp.arange(mb))
    (loss, aux), grads = jax.value_and_grad(trainer._loss, has_aux=True)(state.params, batch)
    hyper = {"clip_eps": pcfg.clip_eps, "vf_coef": pcfg.vf_coef, "ent_coef": pcfg.ent_coef}
    ref_batch = {k: batch[k] for k in ("obs", "action", "logp", "adv", "ret")}
    ref_params = ref.from_policy_params(state.params, TINY)
    ref_loss, ref_grads = ref.ppo_loss_and_grads(ref_params, ref_batch, TINY, hyper)
    blocked_loss, blocked = ref.ppo_loss_and_grads(ref_params, ref_batch, TINY, hyper, block=3)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5)
    np.testing.assert_allclose(blocked_loss, ref_loss, atol=1e-6)
    got = ref.from_policy_params(grads, TINY)
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(ref_grads))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(got)[0]]
    assert any("kda_f" in p for p in paths) and any("head_gate" in p for p in paths)
    for path, a, b, c in zip(paths, jax.tree.leaves(got), jax.tree.leaves(ref_grads),
                             jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, err_msg=path)
        np.testing.assert_allclose(c, b, atol=5e-5 * scale, err_msg=path)
    obs = traj["obs"].reshape(-1, *traj["obs"].shape[2:])
    ref_logits, ref_value = ref.forward(ref_params, obs, TINY)
    ref_logp = jnp.take_along_axis(jax.nn.log_softmax(ref_logits),
                                   traj["action"].reshape(-1, 1), axis=1)[:, 0]
    np.testing.assert_allclose(traj["logp"].reshape(-1), ref_logp, atol=2e-5)
    np.testing.assert_allclose(traj["value"].reshape(-1), ref_value, atol=2e-5)
    assert -5.0 < float(aux["kda_log_decay_mean"]) < 0.0


def test_two_train_steps_in_bfloat16_are_finite_and_carry_the_counters():
    trainer = tiny_trainer("bfloat16")
    state = trainer.init_state(3)
    for _ in range(2):
        state, metrics = trainer._train_step(state)
    assert all(np.isfinite(float(metrics[k])) for k in ("loss", *trainer.policy.COUNTERS))
    assert -5.0 < float(metrics["kda_log_decay_mean"]) < 0.0


# ---------------------------------------------------------------------------
# the router's choice by groups
# ---------------------------------------------------------------------------
def dims_of(**over):
    cfg = {**TINY, **over}
    return mod.Dims(**{k: (v or 0) if k == "q_lora_rank" else v
                       for k, v in cfg.items() if k in mod.Dims._fields})


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
@pytest.mark.parametrize("shape", [(16, 4, 2, 4), (64, 8, 4, 8), (512, 8, 4, 8)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_choice_by_groups_is_the_references(shape, ties):
    n, n_group, keep, k = shape
    choice = jax.random.uniform(jax.random.PRNGKey(n), (200, n), jnp.float32)
    if ties:      # a few distinct values: equal scores inside and across groups
        choice = jnp.round(choice * 5.0) / 5.0
    dims = dims_of(n_routed_experts=n, n_group=n_group, topk_group=keep, num_experts_per_tok=k)
    got = np.asarray(mod.choose(choice, dims))
    want = np.asarray(ref.choose(choice, dict(n_group=n_group, topk_group=keep,
                                              num_experts_per_tok=k)))
    np.testing.assert_array_equal(got, want)            # the same experts in the same order
    per = n // n_group
    assert all(len(set(row // per)) <= keep for row in got)
    # by hand for the first token: the groups' scores, the best of them, the top-k inside
    row = np.asarray(choice[0]).reshape(n_group, per)
    score = np.sort(row, -1)[:, -2:].sum(-1)
    kept = sorted(np.argsort(-score, kind="stable")[:keep])
    inside = np.concatenate([row[g] if g in kept else np.full(per, -np.inf)
                             for g in range(n_group)])
    np.testing.assert_array_equal(got[0], np.argsort(-inside, kind="stable")[:k])


def test_one_group_is_the_plain_top_k_and_the_weights_are_the_scores_not_the_bias():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (50, 16), jnp.float32))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,), jnp.float32)
    dims = dims_of(n_group=1, topk_group=1)
    idx, weights = mod.route(scores, bias, dims)
    np.testing.assert_array_equal(idx, jax.lax.top_k(scores + bias, 4)[1])
    grouped_idx, grouped_weights = mod.route(scores, bias, dims_of())
    picked = jnp.take_along_axis(scores, grouped_idx, axis=-1)
    np.testing.assert_allclose(
        grouped_weights, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    assert not np.array_equal(np.sort(idx, -1), np.sort(grouped_idx, -1))


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Four shares of four of sixteen experts, chosen by groups: their partial
    sums, the shared expert counted once, are the uncut REFERENCE's layer."""
    dims = dims_of(experts_held=16, expert_offset=0)
    layer = mod.ExpertLayer(dims, jnp.float32)
    first = jax.random.normal(jax.random.PRNGKey(4), (80, 64), jnp.float32)
    tokens = jax.random.normal(jax.random.PRNGKey(5), (80, 64), jnp.float32)
    full = layer.init(jax.random.PRNGKey(6), first)["params"]
    cfg = {**TINY, "experts_held": 16, "expert_offset": 0}
    y = ref.rms_norm(tokens, full["ffn_norm"], 1e-6)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.expert_layer(full, y, cfg)
        shared = ref.swiglu(y, full["shared_gate"], full["shared_up"], full["shared_down"], cfg)
    total = jnp.zeros_like(uncut)
    for offset in range(0, 16, 4):
        part = {k: (v[offset:offset + 4] if k.startswith("experts_") else v)
                for k, v in full.items()}
        out, _counters, _idx = mod.ExpertLayer(
            dims._replace(experts_held=4, expert_offset=offset), jnp.float32).apply(
                {"params": part}, tokens)
        total = total + (out - shared)
    np.testing.assert_allclose(total + shared, uncut, atol=3e-5)


# ---------------------------------------------------------------------------
# the layers' own equations
# ---------------------------------------------------------------------------
def test_a_linear_layers_decay_lies_inside_its_bound_and_a_later_bar_changes_nothing_before():
    dims = dims_of()
    layer = mod.KimiDeltaAttention(dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)
    out, decay = layer.apply(params, x)
    assert out.shape == x.shape and -5.0 < float(decay) < 0.0
    later = x.at[:, 30:].set(0.0)
    np.testing.assert_allclose(layer.apply(params, later)[0][:, :30], out[:, :30], atol=1e-6)
    bias = np.asarray(params["params"]["dt_bias"])
    step = -5.0 / (1.0 + np.exp(-bias))          # a channel's log-decay with W_f's part at zero
    assert -1.0 - 1e-6 <= step.min() and step.max() <= -1e-3 + 1e-6   # tau in [1, 1000]
    with pytest.raises(ValueError, match="kda_lower_bound"):
        mod.KimiDeltaAttention(dims._replace(kda_lower_bound=-9.0), jnp.float32).init(
            jax.random.PRNGKey(1), x)


def test_latent_attention_without_the_low_rank_query_path_pads_its_values_and_gates_by_head():
    dims = dims_of()
    layer = mod.LatentAttention(dims, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)
    p = params["params"]
    assert p["q"].shape == (64, 4 * 24) and p["head_gate"].shape == (64, 4)
    assert p["o"].shape == (4 * 16, 64)          # the values' width, not the padded one
    with jax.default_matmul_precision("highest"):
        want = ref.mla(p, ref.rms_norm(x, p["attn_norm"], 1e-6), TINY)
    np.testing.assert_allclose(layer.apply(params, x), want, atol=1e-5)
    open_gate = {"params": {**p, "head_gate": jnp.zeros_like(p["head_gate"])}}
    ungated = mod.LatentAttention(dims._replace(attn_output_gate=False), jnp.float32).apply(
        {"params": {k: v for k, v in p.items() if k != "head_gate"}}, x)
    np.testing.assert_allclose(layer.apply(open_gate, x), 0.5 * ungated, atol=1e-5)


@pytest.mark.parametrize("fault", ["kda_no_decay", "kda_beta_one"])
def test_the_references_controls_change_what_it_computes(fault):
    policy, params, tokens = policy_and_params()
    plain = ref.from_policy_params(params, TINY)
    value = ref.forward(plain, tokens, TINY)[1]
    faulty = ref.forward(plain, tokens, {**TINY, fault: True})[1]
    assert float(jnp.abs(value - faulty).max()) > 1e-3


def test_the_analytic_flops_are_the_benchmarks_required_operations_and_666m_parameters():
    import sys

    from gymfx_tpu.telemetry.mfu import analytic_train_step_flops

    sys.path[:0] = [str(ROOT / "benchmarks")]
    try:
        import harness
        import run as bench_run
        rooflines = harness.load_module("rooflines", "hybrid_decoder")
        cell = bench_run.load_cell("ling3flash_w1024_train", False)
    finally:
        del sys.path[0]
    s = rooflines.sizes(cell)
    policy = make_policy("mla_moe_decoder", dtype=jnp.bfloat16,
                         **cell["config"]["program"]["policy_kwargs"])
    tokens = jnp.zeros((s["window"], 11), jnp.float32)   # 7 z-scored columns + 4 scalars
    shapes = jax.eval_shape(policy.init, jax.random.PRNGKey(0), tokens)
    assert 666e6 < sum(x.size for x in jax.tree.leaves(shapes)) < 668e6
    linear, latent, _dense, _sparse = rooflines.layer_kinds(s)
    analytic = analytic_train_step_flops(
        shapes, num_envs=s["envs"], horizon=s["horizon"], update_epochs=s["epochs"],
        tokens=s["window"], window=s["window"], n_layers=latent, causal=True,
        d_model=s["num_attention_heads"] * (
            s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"]) // 2,
        expert_share=s["num_experts_per_tok"] / s["n_routed_experts"],
        linear_layers=linear, linear_heads=s["num_attention_heads"],
        linear_head_dim=s["kda_head_dim"])
    required = rooflines.train_step_flops(cell)
    assert abs(analytic / required - 1.0) < 2e-3     # the heads and W_in, counted per token
    assert 115e12 < required < 120e12


# ---------------------------------------------------------------------------
# the accepted configuration's trunk, pinned
# ---------------------------------------------------------------------------
GLM_TREE = {
    "Dense_0/bias": ((3,), "float32", 0),
    "Dense_0/kernel": ((64, 3), "float32", 20.2181),
    "Dense_1/bias": ((1,), "float32", 0),
    "Dense_1/kernel": ((64, 1), "float32", 6.20556),
    "dense_0/attn/attn_norm": ((64,), "float32", 64),
    "dense_0/attn/kv_a": ((64, 20), "float32", 128.604),
    "dense_0/attn/kv_a_norm": ((16,), "float32", 16),
    "dense_0/attn/kv_b": ((16, 112), "float32", 366.346),
    "dense_0/attn/o": ((64, 64), "float32", 412.857),
    "dense_0/attn/q_a": ((64, 24), "float32", 150.308),
    "dense_0/attn/q_a_norm": ((24,), "float32", 24),
    "dense_0/attn/q_b": ((24, 64), "float32", 255.616),
    "dense_0/ffn/down": ((160, 64), "float32", 652.336),
    "dense_0/ffn/ffn_norm": ((64,), "float32", 64),
    "dense_0/ffn/gate": ((64, 160), "float32", 1023.69),
    "dense_0/ffn/up": ((64, 160), "float32", 1014.75),
    "final_norm": ((64,), "float32", 64),
    "in_proj": ((11, 64), "float32", 168.841),
    "moe/attn/attn_norm": ((2, 64), "float32", 128),
    "moe/attn/kv_a": ((2, 64, 20), "float32", 254.486),
    "moe/attn/kv_a_norm": ((2, 16), "float32", 32),
    "moe/attn/kv_b": ((2, 16, 112), "float32", 714.431),
    "moe/attn/o": ((2, 64, 64), "float32", 819.364),
    "moe/attn/q_a": ((2, 64, 24), "float32", 306.915),
    "moe/attn/q_a_norm": ((2, 24), "float32", 48),
    "moe/attn/q_b": ((2, 24, 64), "float32", 501.638),
    "moe/experts/e_score_correction_bias": ((2, 16), "float32", 1.15217),
    "moe/experts/experts_down": ((2, 4, 48, 64), "float32", 2834.65),
    "moe/experts/experts_gate": ((2, 4, 64, 48), "float32", 2454.91),
    "moe/experts/experts_up": ((2, 4, 64, 48), "float32", 2451.85),
    "moe/experts/ffn_norm": ((2, 64), "float32", 128),
    "moe/experts/router": ((2, 64, 16), "float32", 209.795),
    "moe/experts/shared_down": ((2, 48, 64), "float32", 703.119),
    "moe/experts/shared_gate": ((2, 64, 48), "float32", 606.824),
    "moe/experts/shared_up": ((2, 64, 48), "float32", 613.391),
}


def test_the_all_latent_trunk_of_the_accepted_configuration_keeps_its_parameter_tree():
    """``ppo_glm47flash_ep8_bf16`` at its rehearsal widths: the tree's paths,
    shapes and dtypes, and the values one key draws (the sum of each leaf's
    magnitudes, as the parent commit drew them)."""
    conf = json.loads((ROOT / "benchmarks/configs/ppo_glm47flash_ep8_bf16.json").read_text())
    kwargs = {**conf["program"]["policy_kwargs"],
              **conf["rehearse"]["program"]["policy_kwargs"]}
    assert (conf["n_group"], conf["topk_group"]) == (1, 1)       # what route() now reads
    policy = make_policy("mla_moe_decoder", dtype=jnp.bfloat16, **kwargs)
    tokens = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 11), jnp.float32)
    params = policy.init(jax.random.PRNGKey(0), tokens)
    got = {"/".join(str(k.key) for k in path[1:]): leaf
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert sorted(got) == sorted(GLM_TREE)
    for name, (shape, dtype, magnitude) in GLM_TREE.items():
        assert (got[name].shape, str(got[name].dtype)) == (shape, dtype), name
        np.testing.assert_allclose(np.abs(np.asarray(got[name], np.float64)).sum(), magnitude,
                                   rtol=2e-5, atol=1e-12, err_msg=name)
    again = policy.init(jax.random.PRNGKey(0), tokens)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params),
                                                    jax.tree.leaves(again)))
