"""The step program's layers by name (gymfx_tpu/telemetry/scopes.py).

  * every layer of the vocabulary that applies occurs in the scope map of
    the step ``bench_util.compile_train_step`` hands out, for an MLP and a
    ``transformer_ring`` and an ``mla_moe_decoder`` trainer; ``update/loss``
    has both directions; an
    unscoped ``while`` or fusion inherits from the computation it calls;
  * scopes are metadata only: the step's metrics and final params are
    bitwise what a trainer traced with ``jax.named_scope`` patched to a
    no-op gives;
  * the registry of the newest step program does no work until it is asked,
    and keeps no executable it no longer needs;
  * the Pallas kernels carry their names into the lowered text.
"""
import contextlib
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.data.feed import MarketDataset
from gymfx_tpu.telemetry import scopes
from tests.helpers import gather_op_paths, uptrend_df

POLICIES = {
    # the env-dynamics kernels interpreted, so that their scopes are there
    "mlp": dict(policy="mlp", policy_kwargs={"hidden": [16, 16]},
                rollout_env_kernel="interpret"),
    "transformer_ring": dict(
        policy="transformer_ring",
        policy_kwargs={"d_model": 16, "n_heads": 2, "n_layers": 1}),
    "mla_moe_decoder": dict(
        policy="mla_moe_decoder",
        policy_kwargs=dict(
            hidden_size=32, q_lora_rank=16, kv_lora_rank=8, num_attention_heads=2,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
            n_layers=2, experts_held=4)),
    # the same trunk with Kimi Delta Attention in the layers its index gives
    "hybrid_decoder": dict(
        policy="mla_moe_decoder",
        policy_kwargs=dict(
            hidden_size=32, q_lora_rank=None, kv_lora_rank=8, num_attention_heads=2,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
            n_group=2, topk_group=1, n_layers=3, experts_held=4, layer_group_size=3,
            attn_output_gate=True, kda_head_dim=16, kda_chunk=16)),
    # the same trunk with its mixers by the published ``layer_types``: gated short
    # convolutions, one grouped-query layer, an expert layer without a shared expert
    "conv_hybrid_decoder": dict(
        policy="mla_moe_decoder",
        policy_kwargs=dict(
            hidden_size=32, num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
            intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
            num_experts_per_tok=2, n_shared_experts=0, n_layers=3, experts_held=4,
            layer_types=["conv", "conv", "full_attention"])),
}
# the parts of a policy's blocks, by policy: the layers that apply to it
BLOCKS = {"mlp": (), "transformer_ring": (scopes.ATTENTION, scopes.FFN),
          "mla_moe_decoder": (scopes.ATTENTION, scopes.FFN) + scopes.MOE_SCOPES,
          "hybrid_decoder": (scopes.ATTENTION, scopes.FFN, scopes.LINEAR_ATTENTION)
          + scopes.MOE_SCOPES,
          "conv_hybrid_decoder": (scopes.ATTENTION, scopes.FFN, scopes.SHORT_CONV,
                                  scopes.MOE_ROUTER, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS)}
ALL_BLOCKS = set().union(*BLOCKS.values())


def layers_of(policy):
    return [layer for layer in scopes.LAYERS
            if layer.split("/")[-1] in BLOCKS[policy]
            or layer.split("/")[-1] not in ALL_BLOCKS]


def make_trainer(policy):
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from

    config = dict(DEFAULT_VALUES)
    config.update(window_size=8, timeframe="M1", num_envs=4, ppo_horizon=4,
                  ppo_epochs=1, ppo_minibatches=2, **POLICIES[policy])
    env = Environment(config, dataset=MarketDataset(uptrend_df(120), config))
    return PPOTrainer(env, ppo_config_from(config))


@pytest.fixture(scope="module")
def handed_out():
    """{policy: (compiled step, its scope map)} through the program's own
    hand-out: compile_train_step registers, last_step_scope_map reads."""
    from gymfx_tpu.bench_util import compile_train_step

    out = {}
    for policy in POLICIES:
        trainer = make_trainer(policy)
        step, _flops = compile_train_step(trainer, trainer.init_state(0))
        out[policy] = (step, scopes.last_step_scope_map())
    return out


# ---------------------------------------------------------------------------
# (a) the vocabulary in the compiled step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy, layer", [
    (policy, layer) for policy in POLICIES for layer in layers_of(policy)])
def test_every_layer_that_applies_is_in_the_steps_scope_map(handed_out, policy, layer):
    _step, scope_map = handed_out[policy]
    assert layer in {scope.path for scope in scope_map.values()}


@pytest.mark.parametrize("policy", POLICIES)
def test_no_path_outside_the_vocabulary_and_every_path_rooted(handed_out, policy):
    _step, scope_map = handed_out[policy]
    known = set(layers_of(policy)) | set(scopes.GROUP_SCOPES)
    assert {scope.path for scope in scope_map.values()} <= known


@pytest.mark.parametrize("policy", POLICIES)
def test_the_loss_has_both_directions_and_the_rollout_none(handed_out, policy):
    step, scope_map = handed_out[policy]
    ways = {}
    for scope in scope_map.values():
        ways.setdefault(scope.path, set()).add(scope.direction)
    forward = scopes.join(scopes.UPDATE, scopes.LOSS, scopes.POLICY_FORWARD)
    if POLICIES[policy]["policy"] == "mla_moe_decoder":
        # XLA:CPU fuses backward ops of the decoder's dispatch with constants
        # it shares with the forward into ONE fusion that has no op_name of
        # its own, so it inherits the path and no direction (one for each run
        # of expert layers: the hybrid trunk has two); no other instruction
        # may lose its direction, and no other policy has one
        lost = [name for name, scope in scope_map.items()
                if scope.path == forward and scope.direction is None]
        assert len(lost) <= (1 if policy == "mla_moe_decoder" else 2)
        defined = [line for line in step.as_text().splitlines()
                   if line.split("=")[0].split()[-1:] in [[f"%{name}"] for name in lost]]
        assert len(defined) == len(lost)
        assert all(" fusion(" in line and "op_name=" not in line for line in defined)
        ways[forward].discard(None)
    assert ways[forward] == {scopes.FWD, scopes.BWD}
    assert scopes.FWD in ways[scopes.join(scopes.UPDATE, scopes.LOSS)]
    for path, found in ways.items():
        if path.split("/")[0] == scopes.ROLLOUT:
            assert found == {None}, path


@pytest.mark.parametrize("policy", POLICIES)
def test_every_scan_of_the_step_is_charged_to_its_phase_or_layer(handed_out, policy):
    # XLA:CPU leaves a scan's `while` its op_name, the TPU compiler does
    # not (there it inherits from its body: the HLO case below); either
    # way the rollout scan is the phase's and the GAE scan its layer's
    step, scope_map = handed_out[policy]
    whiles = [line.split("=")[0].split()[-1].lstrip("%")
              for line in step.as_text().splitlines() if " while(" in line]
    paths = {scope_map[name].path for name in whiles}
    assert scopes.ROLLOUT in paths and scopes.UPDATE in paths
    assert scopes.join(scopes.UPDATE, scopes.GAE) in paths
    # a loop INSIDE the loss (the decoder's scan over its expert layers, the
    # grid of a Pallas kernel interpreted on the CPU) has the loss's direction
    loss = scopes.join(scopes.UPDATE, scopes.LOSS)
    assert all(scope_map[name].direction is None for name in whiles
               if not scope_map[name].path.startswith(loss))


@pytest.mark.parametrize("policy", ["mlp", "transformer_ring"])
def test_no_gather_picks_the_actions_log_probability(handed_out, policy):
    # train/common.picked_logp is a compare and a sum: every gather left in
    # the step, inside a fusion or out, reads the tape or takes a minibatch
    # (the decoder trunk's router keeps a gather of its own under policy_act)
    step, _scope_map = handed_out[policy]
    paths = gather_op_paths(step.as_text())
    assert any(f"/{scopes.TAPE_READ}/" in path for path in paths)
    assert any(f"/{scopes.MINIBATCH_TAKE}/" in path for path in paths)
    assert not [path for path in paths
                if f"/{scopes.POLICY_ACT}/" in path or f"/{scopes.LOSS}/" in path]


HLO = """\
HloModule jit_step

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %inner.1 = f32[4] add(...), metadata={op_name="jit(step)/rollout/while/body/vmap(env_step)/dynamics/add"}
  ROOT %inner.2 = f32[4] multiply(...), metadata={op_name="vmap(env_step)/dynamics/mul"}
}

%body.1 (arg: f32[4]) -> f32[4] {
  %gather.1 = f32[4] gather(...), metadata={op_name="jit(step)/rollout/while/body/vmap(env_step)/tape_read/gather"}
  %fusion.1 = f32[4] fusion(...), kind=kLoop, calls=%fused_computation.1
  %helper.1 = f32[4] add(...), metadata={op_name="jit(step)/rollout/while/body/jit(obs)/add"}
  %kernel_bwd.1 = f32[4] custom-call(...), custom_call_target="tpu_custom_call", backend_config={"x": "metadata={}"}, metadata={op_name="jit(step)/update/loss/transpose(loss)/jvp(policy_forward)/vmap(P)/Enc_0/attention/kernel_bwd/pallas_call" stack_frame_id=3}
}

%cond.1 (arg: f32[4]) -> pred[] {
  %lt.1 = pred[] compare(...), metadata={op_name="jit(step)/rollout/while/cond/lt"}
}

ENTRY %main.1 (a: f32[4]) -> f32[4] {
  %while.1 = f32[4] while(%a), condition=%cond.1, body=%body.1
  %dot.1 = f32[4] dot(...), metadata={op_name="jit(step)/update/while/body/loss/jvp(policy_forward)/vmap(P)/Dense_0/dot_general"}
  %dot.2 = f32[4] dot(...), metadata={op_name="jit(step)/update/loss/transpose(jvp(policy_forward))/vmap(P)/Dense_0/dot_general"}
  %mul.1 = f32[4] multiply(...), metadata={op_name="jit(step)/update/loss/jvp()/mul"}
  %stray.1 = f32[4] add(...), metadata={op_name="jit(step)/attention/add"}
  ROOT %copy.1 = f32[4] copy(%while.1)
}
"""
FORWARD = "update/loss/policy_forward"
PARSED = {
    "gather.1": ("rollout/env_step/tape_read", None),
    # a fusion that lost its metadata inherits from the computation it
    # calls, whose second op lost the outer part of its path
    "fusion.1": ("rollout/env_step/dynamics", None),
    # a jitted helper that happens to be called like a scope is no scope
    "helper.1": ("rollout", None),
    # a custom VJP's backward repeats the scope it is called under
    "kernel_bwd.1": (FORWARD + "/attention", "bwd"),
    "lt.1": ("rollout", None),
    # the loop's body is rollout's by three to one, and spans two layers
    # and the phase itself: it belongs to what those three share
    "while.1": ("rollout", None),
    "dot.1": (FORWARD, "fwd"),
    "dot.2": (FORWARD, "bwd"),
    "mul.1": ("update/loss", "fwd"),
    # below a phase, and two roots would fit: left as it is
    "stray.1": ("attention", None),
}


@pytest.mark.parametrize("name", sorted(PARSED))
def test_scope_map_from_hlo_reads_the_path_and_the_direction(name):
    assert scopes.scope_map_from_hlo(HLO)[name] == PARSED[name]


def test_scope_map_from_hlo_keeps_to_top_level_instructions():
    # a fusion's insides are no trace events; an unscoped copy stays out
    assert set(scopes.scope_map_from_hlo(HLO)) == set(PARSED)


# ---------------------------------------------------------------------------
# (b) metadata only
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", POLICIES)
def test_scopes_change_no_bit_of_the_step(handed_out, policy, monkeypatch):
    step, _scope_map = handed_out[policy]
    trainer = make_trainer(policy)
    state = trainer.init_state(0)
    scoped = trainer._train_step.lower(state)
    for _ in range(2):
        state, metrics = step(state)

    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = make_trainer(policy)
    plain_state = plain.init_state(0)
    lowered = plain._train_step.lower(plain_state)
    # the patch took, and without locations the two programs are one text
    assert f"{scopes.ROLLOUT}/" in scoped.as_text(debug_info=True)
    assert f"{scopes.ROLLOUT}/" not in lowered.as_text(debug_info=True)
    assert lowered.as_text() == scoped.as_text()
    plain_step = lowered.compile()
    for _ in range(2):
        plain_state, plain_metrics = plain_step(plain_state)

    assert set(metrics) == set(plain_metrics)
    for key in metrics:
        np.testing.assert_array_equal(
            np.asarray(metrics[key]), np.asarray(plain_metrics[key]), err_msg=key)
    for got, want in zip(jax.tree.leaves(state.params),
                         jax.tree.leaves(plain_state.params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# (c) the registry of the newest step program
# ---------------------------------------------------------------------------
class FakeExecutable:
    def __init__(self, text):
        self.text, self.asked = text, 0

    def as_text(self):
        self.asked += 1
        return self.text


def test_registering_does_no_work_and_the_map_is_made_once(monkeypatch):
    calls = []
    real = scopes.scope_map_from_hlo
    monkeypatch.setattr(scopes, "scope_map_from_hlo",
                        lambda text: calls.append(len(text)) or real(text))
    exe = FakeExecutable(HLO)
    scopes.register_step(exe)
    assert exe.asked == 0 and calls == []          # an untraced run ends here
    first = scopes.last_step_scope_map()
    assert first["gather.1"].path == "rollout/env_step/tape_read"
    assert scopes.last_step_scope_map() is first
    assert exe.asked == 1 and calls == [len(HLO)]


def test_compile_train_step_registers_and_reads_no_text(monkeypatch):
    from gymfx_tpu import bench_util

    registered, parsed = [], []
    monkeypatch.setattr(scopes, "register_step", registered.append)
    monkeypatch.setattr(scopes, "scope_map_from_hlo", parsed.append)

    class Compiled:
        def cost_analysis(self):
            return {"flops": 2.0}

        def as_text(self):
            raise AssertionError("no HLO text without a traced run")

    class Jitted:
        def lower(self, *args):
            return self

        def compile(self):
            return Compiled()

    class Trainer:
        _train_step = _train_many = Jitted()

    for k in (None, 4):
        compiled, flops = bench_util.compile_train_step(Trainer(), object(), k)
        assert registered[-1] is compiled and flops == 2.0
    assert len(registered) == 2 and parsed == []


def test_the_registry_holds_the_newest_only_and_lets_it_go_when_asked():
    old, new = FakeExecutable(HLO), FakeExecutable(HLO.replace("gather.1", "gather.2"))
    old_ref, new_ref = weakref.ref(old), weakref.ref(new)
    scopes.register_step(old)
    scopes.register_step(new)
    del old
    gc.collect()
    assert old_ref() is None                       # a newer one was handed out
    assert "gather.2" in scopes.last_step_scope_map()
    del new
    gc.collect()
    assert new_ref() is None                       # asked: the map is kept, not it
    assert "gather.2" in scopes.last_step_scope_map()


def test_no_step_handed_out_gives_no_map(monkeypatch):
    monkeypatch.setattr(scopes, "_executable", None)
    monkeypatch.setattr(scopes, "_scope_map", None)
    assert scopes.last_step_scope_map() is None


# ---------------------------------------------------------------------------
# (d) the kernels' names
# ---------------------------------------------------------------------------
def lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_the_attention_kernels_carry_their_names(direction):
    from gymfx_tpu.ops.fused_attention import fused_window_attention

    x = jnp.ones((2, 8, 2, 4), jnp.float32)

    def fwd(q, k, v):
        return fused_window_attention(q, k, v, interpret=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))(q, k, v)

    # the gradient of a sum needs no forward output: the backward alone
    text = lowered_text(fwd if direction == "fwd" else bwd, x, x, x)
    assert (scopes.KERNEL_ATTENTION_FWD in text) == (direction == "fwd")
    assert (scopes.KERNEL_ATTENTION_BWD in text) == (direction == "bwd")


def test_the_env_dynamics_kernels_carry_their_names(handed_out):
    step, scope_map = handed_out["mlp"]
    text = step.as_text()
    dynamics = scopes.join(scopes.ROLLOUT, scopes.ENV_STEP, scopes.DYNAMICS)
    for kernel in (scopes.KERNEL_FILL_BRACKETS, scopes.KERNEL_MARK_REWARD):
        assert f"/{scopes.DYNAMICS}/{kernel}/" in text
    assert dynamics in {scope.path for scope in scope_map.values()}


def test_the_kernel_names_are_six_and_distinct():
    assert len(set(scopes.KERNEL_NAMES)) == len(scopes.KERNEL_NAMES) == 6


def test_a_rematerialised_blocks_path_is_taken_back_and_a_hoisted_constant_rooted():
    """What PR 29's policy brought: a block recomputed in the backward pass
    carries its whole name stack a second time, and constants hoisted out of
    the scanned blocks keep only the phase and the layer."""
    remat = ("jit(_train_step_impl)/update/while/body/closed_call/loss/"
             "transpose(jvp(policy_forward))/MlaMoeDecoderPolicy/loss/jvp(policy_forward)/"
             "MlaMoeDecoderPolicy/checkpoint/rematted_computation/moe/attention/dot_general")
    assert scopes._op_scope(remat, scopes.SCOPE_NAMES) == scopes.OpScope(
        "update/loss/policy_forward/attention", scopes.BWD)
    hoisted = "jit(_train_step_impl)/update/while/body/closed_call/moe/experts/moe_dispatch/iota"
    assert scopes._op_scope(hoisted, scopes.SCOPE_NAMES).path == \
        "update/loss/policy_forward/moe_dispatch"
    assert scopes._rooted("update/loss") == "update/loss"
    assert scopes._rooted("rollout/nowhere") == "rollout/nowhere"


def test_an_op_merged_from_several_sources_takes_the_last_ones_path():
    """With ``random_episode_start`` the rollout's vmapped reset leaves ops whose
    ``op_name`` lists every source, ``a/x;a/y``: one such name emptied the whole
    scope map (PR 29, the traced rehearsal of ``glm47flash_w256_train``)."""
    merged = ("jit(_train_step_impl)/rollout/vmap()/broadcast_in_dim;"
              "jit(_train_step_impl)/rollout/policy_act/broadcast_in_dim")
    assert scopes._op_scope(merged, scopes.SCOPE_NAMES) == scopes.OpScope(
        "rollout/policy_act", None)
    text = ('ENTRY %main (p: f32[4]) -> f32[4] {\n'
            '  %p = f32[4]{0} parameter(0)\n'
            f'  ROOT %b = f32[4]{{0}} add(%p, %p), metadata={{op_name="{merged}"}}\n'
            '}\n')
    assert scopes.scope_map_from_hlo(text) == {"b": scopes.OpScope("rollout/policy_act", None)}
