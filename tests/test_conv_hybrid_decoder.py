"""The decoder trunk whose blocks take their mixer by the published ``layer_types``
(``gymfx_tpu/train/mla_moe_decoder.py``: gated short convolutions with one
grouped-query attention layer a period, an expert layer WITHOUT a shared expert)
against its plain reference (``gymfx_tpu/reference/hybrid_decoder.py``) at tiny
widths on the CPU: each new mixer alone, forward and gradients, float32 tight and
bfloat16 within a stated bound; causality; grouped-query attention through the
interpreted kernel's packed causal route; the whole policy; the layers' runs; the
shares of an expert-parallel layer; the benchmark configuration's parameter count."""
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
CONFIG = ROOT / "benchmarks" / "configs" / "ppo_lfm2moe_ep8_bf16.json"
# the published block at tiny widths: one leading dense layer and one period
# (conv, conv, conv, attention) behind it; 8 query heads on 2 key-value heads;
# 16 experts, top-4, 4 held, no shared expert
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2, conv_L_cache=3,
            intermediate_size=160, moe_intermediate_size=48, n_routed_experts=16,
            num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0,
            rms_norm_eps=1e-5, rope_theta=1e6, first_k_dense_replace=1, n_layers=5,
            experts_held=4, expert_offset=4,
            layer_types=["conv", "conv", "conv", "conv", "full_attention"])
MIXERS = {"conv": (mod.ShortConv, ref.gated_conv, "conv"),
          "full_attention": (mod.GroupedQueryAttention, ref.gqa, "self_attn")}


def policy_and_params(dtype=jnp.float32, seed=0, window=40, **over):
    """The policy, parameters initialised on ONE batch and tokens of ANOTHER
    (the choice bias is balanced on the first: its tokens sit on the experts'
    thresholds, where a rounding error flips a choice)."""
    policy = make_policy("mla_moe_decoder", dtype=dtype, **{**TINY, **over})
    first = jax.random.normal(jax.random.PRNGKey(seed + 2), (3, window, 5), jnp.float32)
    tokens = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, window, 5), jnp.float32)
    return policy, policy.init(jax.random.PRNGKey(seed), first), tokens


def dims_of(**over):
    cfg = {**TINY, **over}
    return mod.Dims(**{k: v for k, v in cfg.items() if k in mod.Dims._fields})


def mixer_alone(kind, dtype=jnp.float32):
    """(``apply(params, x) -> out``, the reference's ``(leaves, x) -> out`` on the
    module's own parameters, parameters, x (2, 24, hidden))."""
    module_cls, reference, name = MIXERS[kind]
    module = module_cls(dims_of(), dtype)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, TINY["hidden_size"]), jnp.float32)
    params = module.init(jax.random.PRNGKey(4), x)
    # scale the norms' weights and the taps off their initial values
    params = jax.tree.map(lambda a: a * (1.0 + 0.1 * jnp.cos(jnp.arange(a.size).reshape(a.shape))),
                          params)

    def apply(params, x):
        out = module.apply(params, x.astype(dtype))
        return (out[0] if isinstance(out, tuple) else out).astype(jnp.float32)

    def want(params, x):
        wrapped = {"params": {"in_proj": jnp.zeros((1, 1)), "final_norm": jnp.zeros(1),
                              "Dense_0": {"kernel": jnp.zeros((1, 1)), "bias": jnp.zeros(1)},
                              "Dense_1": {"kernel": jnp.zeros((1, 1)), "bias": jnp.zeros(1)},
                              "dense_0": {name: params["params"], "ffn": {}}}}
        cfg = {**TINY, "n_layers": 1, "layer_types": [kind]}
        leaves = jax.tree.map(lambda a: a[0], ref.from_policy_params(wrapped, cfg)["runs"][0])
        with jax.default_matmul_precision("highest"):
            return reference(leaves, ref.rms_norm(x, leaves["attn_norm"], TINY["rms_norm_eps"]),
                             cfg)

    return apply, want, params, x


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# ---------------------------------------------------------------------------
# the two new mixers alone
# ---------------------------------------------------------------------------
# bfloat16 rounds every operand to 2^-8 of itself: a norm, two or three products
# and a softmax or a gate between them leave a few parts in a thousand of the
# output and twice that of a gradient; 0.02 and 0.04 are several times the readings
@pytest.mark.parametrize("dtype, forward_bound, gradient_bound",
                         [(jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 0.02, 0.04)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", list(MIXERS))
def test_a_mixer_alone_is_the_references_forward_and_gradients(kind, dtype, forward_bound,
                                                               gradient_bound):
    apply, want, params, x = mixer_alone(kind, dtype)
    assert rel(apply(params, x), want(params, x)) < forward_bound
    pick = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    got = jax.grad(lambda p, x: jnp.sum(apply(p, x) * pick), argnums=(0, 1))(params, x)
    ref_grads = jax.grad(lambda p, x: jnp.sum(want(p, x) * pick), argnums=(0, 1))(params, x)
    for path, a, b in zip(
            [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(got)[0]],
            jax.tree.leaves(got), jax.tree.leaves(ref_grads)):
        assert rel(a, b) < gradient_bound, path


@pytest.mark.parametrize("kind", list(MIXERS))
def test_a_change_at_a_later_position_leaves_every_earlier_output_bit_equal(kind):
    apply, _want, params, x = mixer_alone(kind)
    t = 13
    later = x.at[:, t + 1:].add(jax.random.normal(jax.random.PRNGKey(3), x[:, t + 1:].shape))
    before, after = apply(params, x), apply(params, later)
    np.testing.assert_array_equal(np.asarray(before[:, :t + 1]), np.asarray(after[:, :t + 1]))
    assert not np.array_equal(np.asarray(before[:, t + 1:]), np.asarray(after[:, t + 1:]))


def test_the_convolutions_first_positions_see_zeros_before_the_window():
    """y_0 = taps[-1] (B u)_0 alone, y_1 = taps[-2] (B u)_0 + taps[-1] (B u)_1."""
    from gymfx_tpu.ops.kda_chunk_scan import causal_conv

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    y = causal_conv(x, taps)
    np.testing.assert_allclose(y[:, 0], taps[2] * x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(y[:, 1], taps[1] * x[:, 0] + taps[2] * x[:, 1], rtol=1e-6)
    np.testing.assert_allclose(y, ref.short_conv(x, taps), rtol=1e-6, atol=1e-6)


def test_grouped_query_heads_through_the_interpreted_kernels_packed_causal_route():
    """Two 64-wide heads a lane group, causal, at a window the trainer hands to the
    kernel: k and v repeated to the query heads against ``full_attention`` on the
    same repeated heads, forward and gradients."""
    from gymfx_tpu.ops.fused_attention import (
        MIN_FUSED_WINDOW,
        fused_window_attention,
        packed_lanes,
    )
    from gymfx_tpu.parallel.ring_attention import full_attention

    heads, kv_heads, width, window = 4, 2, 64, MIN_FUSED_WINDOW
    assert packed_lanes(heads, width) == packed_lanes(32, 64) == 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, window, heads, width), jnp.float32)
    k, v = (jax.random.normal(key, (2, window, kv_heads, width), jnp.float32)
            for key in keys[1:3])
    pick = jax.random.normal(keys[3], q.shape, jnp.float32)

    def through(attend):
        def run(q, k, v):
            k, v = (jnp.repeat(t, heads // kv_heads, axis=-2) for t in (k, v))
            return jnp.sum(attend(q, k, v) * pick)
        return jax.value_and_grad(run, argnums=(0, 1, 2))

    got, got_grads = through(
        lambda q, k, v: fused_window_attention(q, k, v, causal=True, interpret=True))(q, k, v)
    want, want_grads = through(lambda q, k, v: full_attention(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape            # the repeat's gradient: a sum over each group
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))))


# ---------------------------------------------------------------------------
# the layers' kinds and runs
# ---------------------------------------------------------------------------
def test_the_runs_of_the_published_forty_layers_and_of_the_cut():
    published = ["conv", "conv", "full_attention"] + ["conv", "conv", "conv", "full_attention"] * 9
    published += ["conv"]
    if CONFIG.exists():
        assert json.loads(CONFIG.read_text())["layer_types"] == published
    runs = mod.layer_runs(mod.layer_kinds(40, layer_types=published), 2)
    assert runs[:4] == [("dense_0", 0, 1, False, mod.CONV), ("dense_1", 1, 1, False, mod.CONV),
                        ("moe_2", 2, 1, True, mod.FULL), ("moe_3", 3, 3, True, mod.CONV)]
    assert len(runs) == 2 + 20 and runs[-1] == ("moe_39", 39, 1, True, mod.CONV)
    assert sum(run[2] for run in runs if run[4] == mod.CONV) == 30
    assert sum(run[2] for run in runs if run[4] == mod.FULL) == 10
    cut = mod.layer_runs(mod.layer_kinds(5, layer_types=TINY["layer_types"]), 1)
    assert cut == [("dense_0", 0, 1, False, mod.CONV), ("moe_1", 1, 3, True, mod.CONV),
                   ("moe_4", 4, 1, True, mod.FULL)]
    assert [run[1:] for run in cut] == ref.layer_runs(TINY)
    with pytest.raises(ValueError, match="layer_types"):
        mod.layer_kinds(4, layer_types=TINY["layer_types"])
    with pytest.raises(ValueError, match="sliding"):
        mod.layer_kinds(1, layer_types=["sliding"])


def test_the_parameter_tree_has_the_mixers_by_kind_and_no_shared_expert():
    policy, params, _ = policy_and_params()
    tree = params["params"]
    assert set(tree["dense_0"]) == {"conv", "ffn"} and set(tree["moe_1"]) == {"conv", "experts"}
    assert set(tree["moe_4"]) == {"self_attn", "experts"}
    assert {k: v.shape for k, v in tree["moe_1"]["conv"].items()} == {
        "attn_norm": (3, 64), "in_proj": (3, 64, 192), "taps": (3, 3, 64),
        "out_proj": (3, 64, 64)}                                   # three layers, one scan
    assert {k: v.shape for k, v in tree["moe_4"]["self_attn"].items()} == {
        "attn_norm": (1, 64), "q": (1, 64, 64), "k": (1, 64, 16), "v": (1, 64, 16),
        "q_norm": (1, 8), "k_norm": (1, 8), "o": (1, 64, 64)}
    # n_shared_experts 0: no zero-width parameter, no shared expert at all
    assert set(tree["moe_1"]["experts"]) == {
        "ffn_norm", "router", "e_score_correction_bias", "experts_gate", "experts_up",
        "experts_down"}
    assert not [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
                if "shared" in jax.tree_util.keystr(p)]
    assert policy.takes_batch and policy.initial_carry() == ()     # served on the bucket as it is
    json_list = make_policy("mla_moe_decoder", **TINY)             # the JSON list is hashable
    assert hash(json_list) == hash(policy) and json_list.layer_types == tuple(TINY["layer_types"])


def test_the_counters_hold_the_convolutions_gate_rms():
    policy, params, tokens = policy_and_params()
    assert policy.COUNTERS == ("moe_held_share", "moe_load_max_over_mean", "moe_short_buffer_share",
                               "short_conv_gate_rms")
    _, _, counted = policy.apply(params, tokens, counters=True)
    assert set(counted) == set(policy.COUNTERS)
    # by hand over the four convolution layers of the reference's own forward
    leaves = ref.from_policy_params(params, TINY)
    x = tokens @ leaves["in_proj"]
    read = []
    for run, (_first, layers, sparse, kind) in zip(leaves["runs"], ref.layer_runs(TINY)):
        for l in range(layers):
            p = jax.tree.map(lambda a: a[l], run)
            y = ref.rms_norm(x, p["attn_norm"], TINY["rms_norm_eps"])
            if kind == "conv":
                b, _c, u = jnp.split(y @ p["conv_in_proj"], 3, axis=-1)
                read.append(jnp.sqrt(jnp.mean(jnp.square(b * u))))
            x = x + ref.MIXERS[kind](p, y, TINY)
            y = ref.rms_norm(x, p["ffn_norm"], TINY["rms_norm_eps"])
            x = x + (ref.expert_layer(p, y.reshape(-1, 64), TINY)[0].reshape(x.shape) if sparse
                     else ref.swiglu(y, p["gate"], p["up"], p["down"], TINY))
    assert len(read) == 4
    np.testing.assert_allclose(counted["short_conv_gate_rms"], np.mean(read), rtol=1e-4)
    dead = jax.tree.map(lambda a: a, params)
    for run in ("dense_0", "moe_1"):
        dead["params"][run]["conv"]["in_proj"] = jnp.zeros_like(
            params["params"][run]["conv"]["in_proj"])
    assert float(policy.apply(dead, tokens, counters=True)[2]["short_conv_gate_rms"]) == 0.0


# ---------------------------------------------------------------------------
# the whole policy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("over", [{}, {"experts_held": 16, "expert_offset": 0}],
                         ids=["share", "all_held"])
def test_logits_values_and_choices_are_the_references(over):
    policy, params, tokens = policy_and_params(**over)
    cfg = {**TINY, **over}
    logits, value, chosen = policy.apply(params, tokens, routing=True)
    want_logits, want_value, want_chosen = ref.forward(
        ref.from_policy_params(params, cfg), tokens, cfg, with_routing=True)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(value, want_value, atol=2e-5)
    assert chosen.shape == want_chosen.shape == (4, 3 * 40, 4)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want_chosen, -1))


@pytest.mark.parametrize("fault", ["conv_no_mixing", "gqa_kv_by_modulo", "gqa_no_qk_norm"])
def test_a_fault_laid_on_the_reference_moves_its_forward(fault):
    """What the benchmark's check lays on the reference as a control is not a
    no-op: each moves the logits far beyond the float32 agreement."""
    policy, params, tokens = policy_and_params()
    logits, _ = policy.apply(params, tokens)
    leaves = ref.from_policy_params(params, TINY)
    faulty, _ = ref.forward(leaves, tokens, {**TINY, fault: True})
    assert float(jnp.max(jnp.abs(faulty - logits))) > 1e-3


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
    assert float(aux["short_conv_gate_rms"]) > 0.0
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
    assert any("conv_taps" in p for p in paths) and any("gqa_k_norm" in p for p in paths)
    for path, a, b, c in zip(paths, jax.tree.leaves(got), jax.tree.leaves(ref_grads),
                             jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, err_msg=path)
        np.testing.assert_allclose(c, b, atol=5e-5 * scale, err_msg=path)


# ---------------------------------------------------------------------------
# the chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """64 experts over 8 chips (offsets 0, 8, ... 56), top-4, NO shared expert:
    the shares' partial results sum to the reference's whole layer, nothing
    counted twice."""
    over = dict(n_routed_experts=64, experts_held=8)
    x = jax.random.normal(jax.random.PRNGKey(7), (96, TINY["hidden_size"]), jnp.float32)
    whole = mod.ExpertLayer(dims_of(**{**over, "experts_held": 64, "expert_offset": 0}),
                            jnp.float32)
    whole_params = whole.init(jax.random.PRNGKey(6), x)["params"]
    total = 0.0
    for offset in range(0, 64, 8):
        part = {k: (v[offset:offset + 8] if k.startswith("experts_") else v)
                for k, v in whole_params.items()}
        out, _counters, idx = mod.ExpertLayer(
            dims_of(**{**over, "expert_offset": offset}), jnp.float32).apply({"params": part}, x)
        total = total + out
    cfg = {**TINY, **over, "experts_held": 64, "expert_offset": 0}
    with jax.default_matmul_precision("highest"):
        y = ref.rms_norm(x, whole_params["ffn_norm"], TINY["rms_norm_eps"])
        want, want_idx = ref.expert_layer(whole_params, y, cfg)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the benchmark's configuration at its published widths (shapes only)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not CONFIG.exists(), reason="the benchmark's configuration is not here")
def test_the_benchmark_configuration_holds_452_million_parameters_at_the_published_widths():
    conf = json.loads(CONFIG.read_text())
    kwargs = conf["program"]["policy_kwargs"]
    assert (kwargs["hidden_size"], kwargs["num_attention_heads"], kwargs["num_key_value_heads"],
            kwargs["conv_L_cache"], kwargs["intermediate_size"], kwargs["moe_intermediate_size"],
            kwargs["n_routed_experts"], kwargs["num_experts_per_tok"],
            kwargs["n_shared_experts"]) == (2048, 32, 8, 3, 11776, 1536, 64, 4, 0)
    policy = make_policy("mla_moe_decoder", dtype=jnp.bfloat16, **kwargs)
    shapes = jax.eval_shape(policy.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((2, 64, 11), jnp.float32))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 452e6 < count < 453e6, count
    assert shapes["params"]["moe_4"]["self_attn"]["q_norm"].shape[-1] == 64
