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
  * the Pallas kernels carry their names into the lowered text;
  * the parts of a block (PR 37) lie under their layers in every pass in the
    PART map, and the layer map is the map of the step without them.
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


# ---------------------------------------------------------------------------
# (e) the parts of a block and the part map (PR 37)
# ---------------------------------------------------------------------------
# the trunk with every kind of mixer in ONE step: a dense linear-attention
# layer, then a run each of convolution, grouped-query and latent attention
ALL_KINDS = dict(
    policy="mla_moe_decoder",
    policy_kwargs=dict(
        hidden_size=32, q_lora_rank=None, kv_lora_rank=8, num_attention_heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
        moe_intermediate_size=16, n_routed_experts=8, num_experts_per_tok=2,
        n_group=2, topk_group=1, n_layers=4, experts_held=4, num_key_value_heads=1,
        attn_output_gate=True, kda_head_dim=16, kda_chunk=16, conv_L_cache=3,
        layer_types=["linear_attention", "conv", "full_attention", "latent_attention"]))


def parts_patched_out(monkeypatch):
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext()
                        if name in scopes.PART_NAMES else real(name))


@pytest.fixture(scope="module")
def parted():
    """The all-kinds step through the program's hand-out, its two maps and its
    op names, and the layer map of the same step traced with the parts'
    scopes patched out (what the layer map was before the parts)."""
    from gymfx_tpu.bench_util import compile_train_step

    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setitem(POLICIES, "all_kinds", ALL_KINDS)
    try:
        trainer = make_trainer("all_kinds")
        step, _flops = compile_train_step(trainer, trainer.init_state(0))
        layer_map, part_map = scopes.last_step_scope_map(), scopes.last_step_part_map()
        op_names = scopes.scope_map_from_hlo(step.as_text(), None)
        parts_patched_out(monkeypatch)
        plain = make_trainer("all_kinds")
        plain_map = scopes.scope_map_from_hlo(
            plain._train_step.lower(plain.init_state(0)).compile().as_text())
    finally:
        monkeypatch.undo()
    return layer_map, part_map, op_names, plain_map


FORWARD_PATH = scopes.join(scopes.UPDATE, scopes.LOSS, scopes.POLICY_FORWARD)
PART_LAYERS = [(scopes.KDA_SCAN, scopes.LINEAR_ATTENTION),
               (scopes.CAUSAL_CONV, scopes.LINEAR_ATTENTION),
               (scopes.CAUSAL_CONV, scopes.SHORT_CONV),
               (scopes.ATTENTION_CORE, scopes.ATTENTION)]
# where a part's ops lie: the rollout, the update's two directions, the
# forward recomputed in the backward pass under nn.remat, and the backward
# pass of a custom VJP (its own scope behind the call site's)
WHERE = {
    "rollout": lambda op_name, scope: scope.direction is None,
    "update_fwd": lambda op_name, scope: scope.direction == scopes.FWD,
    "update_bwd": lambda op_name, scope: scope.direction == scopes.BWD,
    "remat": lambda op_name, scope: (scope.direction == scopes.BWD
                                     and "rematted_computation" in op_name),
}


@pytest.mark.parametrize("part, layer, where", [
    (part, layer, where) for part, layer in PART_LAYERS for where in WHERE]
    + [(scopes.KDA_SCAN, scopes.LINEAR_ATTENTION, "custom_vjp_bwd")])
def test_each_part_lies_under_its_layer_in_every_pass(parted, part, layer, where):
    _layer_map, part_map, op_names, _plain = parted
    phase = (scopes.join(scopes.ROLLOUT, scopes.POLICY_ACT) if where == "rollout"
             else FORWARD_PATH)
    path = scopes.join(phase, layer, part)
    if where == "custom_vjp_bwd":
        def wanted(op_name, scope):
            return scope.direction == scopes.BWD and f"/{part}/{part}/" in op_name
    else:
        wanted = WHERE[where]
    assert [name for name, scope in part_map.items()
            if scope.path == path and name in op_names
            and wanted(op_names[name].path, scope)]


def test_the_layer_map_holds_no_part_and_is_the_map_without_the_parts(parted):
    layer_map, _part_map, _op_names, plain_map = parted
    assert not {name for scope in layer_map.values() for name in scope.path.split("/")
                } & set(scopes.PART_NAMES)
    assert layer_map == plain_map


def test_every_part_path_extends_its_layer_path(parted):
    layer_map, part_map, _op_names, _plain = parted
    for name, scope in layer_map.items():
        got = part_map[name]
        assert got.direction == scope.direction, name
        assert got.path == scope.path or (
            got.path.startswith(scope.path + "/")
            and got.path.split("/")[-1] in scopes.PART_NAMES), (name, got, scope)
    # what the part map names beyond the layer map, the rule named
    assert all(name.split(".")[0] in ("copy", "copy-start", "copy-done")
               for name in set(part_map) - set(layer_map))


ASYNC_HLO = """\
HloModule jit_step

ENTRY %main.1 (a: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %copy-start.1 = (f32[4]{0:T(256)S(1)}, f32[4]{0}, u32[]{:S(2)}) copy-start(%p.1)
  %copy-done.1 = f32[4]{0:T(256)S(1)} copy-done(%copy-start.1)
  %scan.1 = f32[4]{0} dot(%copy-done.1, %p.1), metadata={op_name="jit(step)/update/loss/jvp(policy_forward)/M/linear_attention/kda/kda_scan/dot_general"}
  %proj.1 = f32[4]{0} dot(%p.1, %copy-done.1), metadata={op_name="jit(step)/update/loss/jvp(policy_forward)/M/linear_attention/kda/dot_general"}
  %slice-start.1 = ((f32[8]{0}), f32[4]{0}, s32[]) slice-start(%p.1)
  %slice-done.1 = f32[4]{0} slice-done(%slice-start.1)
  %copy.1 = f32[4]{0} copy(%slice-done.1)
  %core.1 = f32[4]{0} add(%copy.1, %scan.1), metadata={op_name="jit(step)/rollout/policy_act/M/attention/attention_core/add"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%proj.1)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  %moved.1 = f32[4]{0} copy(%core.1), metadata={op_name="jit(step)/jit(helper)/copy"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}, f32[4]{0}) tuple(%copy-done.2, %core.1, %moved.1)
}
"""
LINEAR = "update/loss/policy_forward/linear_attention"
ASYNC_PARTS = {
    "scan.1": (LINEAR + "/kda_scan", "fwd"),
    "proj.1": (LINEAR, "fwd"),
    "core.1": ("rollout/policy_act/attention/attention_core", None),
    # a done takes what its users share, its start the done's
    "copy-done.1": (LINEAR, "fwd"),
    "copy-start.1": (LINEAR, "fwd"),
    # a copy takes its user's, the done behind it the copy's, and so on back
    "copy.1": ("rollout/policy_act/attention/attention_core", None),
    "slice-done.1": ("rollout/policy_act/attention/attention_core", None),
    "slice-start.1": ("rollout/policy_act/attention/attention_core", None),
}


@pytest.mark.parametrize("name", sorted(ASYNC_PARTS))
def test_the_part_map_names_async_pairs_and_copies_after_their_users(name):
    part_map, _unnamed = scopes.part_map_from_hlo(ASYNC_HLO)
    assert part_map[name] == ASYNC_PARTS[name]


@pytest.mark.parametrize("name, opcode", [
    # a done whose one user is unnamed stays unnamed, and its start with it;
    # a copy WITH metadata is the program's own, not one XLA added
    ("copy-done.2", "copy-done"), ("copy-start.2", "copy-start"), ("moved.1", "copy"),
    ("tuple.1", "tuple"), ("p.1", "parameter")])
def test_what_the_part_map_cannot_name_is_handed_out_with_its_opcode(name, opcode):
    part_map, unnamed = scopes.part_map_from_hlo(ASYNC_HLO)
    assert name not in part_map and unnamed[name] == opcode
    assert set(part_map) | set(unnamed) == set(ASYNC_PARTS) | {
        "copy-done.2", "copy-start.2", "moved.1", "tuple.1", "p.1"}


def test_the_layer_map_takes_no_part_and_names_no_copy():
    assert scopes.scope_map_from_hlo(ASYNC_HLO) == {
        "scan.1": (LINEAR, "fwd"), "proj.1": (LINEAR, "fwd"),
        "core.1": ("rollout/policy_act/attention", None)}
    assert scopes.part_map_from_hlo("no HLO (") == ({}, {})


def test_both_maps_come_from_one_text_and_the_executable_is_let_go():
    exe = FakeExecutable(ASYNC_HLO)
    ref = weakref.ref(exe)
    scopes.register_step(exe)
    assert exe.asked == 0
    part_map = scopes.last_step_part_map()
    assert part_map["copy-done.1"].path == LINEAR
    assert scopes.last_step_scope_map()["core.1"].path == "rollout/policy_act/attention"
    assert scopes.last_step_unnamed()["tuple.1"] == "tuple"
    assert exe.asked == 1 and scopes.last_step_part_map() is part_map
    assert set(scopes.build_cost) == {"scope_map_s", "part_map_s", "text_bytes"}
    del exe
    gc.collect()
    assert ref() is None
    scopes.register_step(FakeExecutable(HLO))       # a newer step: new maps
    assert "copy-done.1" not in scopes.last_step_part_map()
