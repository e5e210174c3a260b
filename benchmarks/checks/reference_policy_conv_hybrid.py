"""``correct`` for a trainer cell whose policy is the decoder trunk with gated
short convolutions and grouped-query attention (its mixers by the published
``layer_types``): ``checks/reference_policy_hybrid.py`` as it stands -- and through
it ``checks/reference_policy.py``: the twin's bit-for-bit repeat, the recorded
log-probabilities and values, the routing flips, the step's loss, the parameters'
change over all minibatches and its size (``update_norm_shortfall``) -- with the
parameter groups of the reference-shaped tree of
``checks/conv_hybrid_decoder_reference.py`` among the candidates for the worst
group: the convolution's one projection in, its taps, its projection out, and the
grouped-query layer's q / k / v, its output product and its two head norms.  That
file judges an update by a module global of the copy of ``reference_policy`` it
loaded; the further groups are laid on that copy.

``reference_policy.py`` puts ``controls.faults`` through the forward's distances
alone.  A fault that the forward hides can still show in the update: a program
that holds a parameter and does not use it gives it no gradient, so Adam leaves
it where it was and its group reads 1.  With ``controls.update_faults`` (names of
``controls.faults``) the reference's OWN update under each of those faults is
held against its plain update, over the step's minibatches, and put through the
limits with the fault's other distances."""
import gc

import harness

_hybrid = harness.load_module("checks", "reference_policy_hybrid")
_base = _hybrid._base
_base.GROUPS = {
    **_base.GROUPS,
    "conv_in": ("conv_in_proj",), "conv_taps": ("conv_taps",), "conv_out": ("conv_out_proj",),
    "gqa_qkv": ("gqa_q", "gqa_k", "gqa_v"), "gqa_out": ("gqa_o",),
    "gqa_head_norms": ("gqa_q_norm", "gqa_k_norm"),
}
verdict = _hybrid.verdict


def reference_updates(ctx, spec, trainer, faults):
    """{fault: the update distances of the reference under it from the plain
    reference}: each side's update from ``init_state(seed)``'s parameters over
    the minibatches of that state's step (``reference_policy.reference`` (c):
    the step's plan and permutation, each side's old log-probabilities its own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gymfx_tpu.train.common import minibatch_plan

    ref = harness.load_module("checks", spec["reference"])
    pcfg = trainer.pcfg
    cfg = {"first_k_dense_replace": 1, "norm_topk_prob": True, "expert_offset": 0,
           "n_shared_experts": 1, **dict(pcfg.policy_kwargs)}
    cfg["experts_held"] = cfg.get("experts_held") or cfg["n_routed_experts"]
    hyper = {"clip_eps": pcfg.clip_eps, "vf_coef": pcfg.vf_coef, "ent_coef": pcfg.ent_coef,
             "lr": pcfg.lr, "max_grad_norm": pcfg.max_grad_norm}
    state = trainer.init_state(harness.seed31(ctx.seed))
    before = ref.from_policy_params(jax.tree.map(np.asarray, jax.device_get(state.params)), cfg)

    def walk(state):
        inter, out = trainer._rollout_phase(state)
        return inter._replace(params=(), opt_state=()), out

    inter, (traj, last_value) = jax.jit(walk)(state)
    advs, returns = trainer._gae(traj, last_value)
    steps, envs = traj["logp"].shape
    fields = {"obs": traj["obs"], "action": traj["action"], "adv": advs, "ret": returns,
              "sample": jnp.arange(steps * envs, dtype=jnp.int32).reshape(steps, envs)}
    n_perm, mb, take = minibatch_plan(
        fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
        horizon=pcfg.horizon, minibatches=pcfg.minibatches)
    perm = jax.random.permutation(jax.random.split(inter.rng, pcfg.epochs + 1)[1], n_perm)
    batches = [take(jax.lax.dynamic_slice_in_dim(perm, i * mb, mb))
               for i in range(pcfg.minibatches)]
    obs = traj["obs"].reshape(-1, *traj["obs"].shape[2:])
    action, block = traj["action"].reshape(-1), int(spec["block_decisions"])
    del state, inter, traj, fields, advs, returns
    gc.collect()

    def updated(cfg):
        """The reference's parameters (on the host) after its own update under ``cfg``."""
        params = jax.tree.map(jnp.asarray, before)
        forward = jax.jit(lambda p, o: jax.nn.log_softmax(ref.forward(p, o, cfg)[0]))
        logp = jnp.concatenate([
            jnp.take_along_axis(forward(params, obs[at:at + block]),
                                action[at:at + block, None], axis=1)[:, 0]
            for at in range(0, obs.shape[0], block)])
        moments = ref.adam_init(params)
        for batch in batches:
            batch = dict(batch)
            batch["logp"] = logp[batch.pop("sample")]
            _, grads = ref.ppo_loss_and_grads(
                params, batch, cfg, hyper, block=int(spec["block_samples"]))
            params, moments = ref.adam_update(params, grads, moments, hyper)
            del grads
        return jax.tree.map(np.asarray, params)

    plain = updated(cfg)
    return {name: _base.update_distance(updated({**cfg, **over}), plain, before)
            for name, over in faults.items()}


def reference(ctx, spec, build, first_steps):
    controls = spec.get("controls") or {}
    faults = {name: controls["faults"][name] for name in controls.get("update_faults", ())}
    built = []

    def kept():
        built.append(build())
        return built[-1]

    out = _hybrid.reference(ctx, spec, kept if faults else build, first_steps)
    if faults:
        for name, part in reference_updates(ctx, spec, built.pop(), faults).items():
            read = out["controls"][name]
            read.update(part)
            read["refused_by"] = _base.over_limits(spec["limits"], read)
    return out
