"""``correct`` for a trainer cell whose policy has a plain reference
(``checks/<spec["reference"]>.py``, the benchmark's own copy): the measured
program's compiled step -- its rollout, its loss, its optimizer, every
minibatch -- against the reference in float32 at ``highest`` precision.

The object compared is the object timed.  ``first_steps(build())`` compiles
the cell's step program and runs its warm-up steps from ``--seed`` exactly as
the runner does next for the trainer it times; ``verdict`` holds the two runs'
losses and parameters equal (``twin``: same program, same seed).  That compiled
step then takes ONE more step, from ``init_state(seed)`` (the configuration
starts every env at an offset of its own, ``random_episode_start``: the windows
hold bars; on a window of padding the tokens are identical and attention returns
v whatever q, k and the positions are), and that step is what the reference is
held against:

  (a) rollout: the trajectory of that step (the trainer's ``_rollout_phase``
      from the same state: same rng, same actions; ``same_rollout`` says that
      the step's own rollout ended in the same env states): the values and
      log-probabilities it recorded against the reference's on the
      observations it recorded, in blocks of decisions;
  (b) routing: the reference routes on its OWN scores; the share of token
      choices on which the trainer's policy and the reference disagree
      (``routing_flips``, a choice counted once however it sorts);
  (c) update: the change of the parameters over the step, ``p1 - p0``, and the
      step's loss (the mean of its minibatches'), against the reference's own update over the same
      minibatches (the step's own permutation) -- its PPO loss, ``jax.grad``
      of its forward summed over blocks of samples, its clip and Adam, one
      minibatch after another from ``p0``; each side's old log-probabilities
      are its OWN (ratio 1 on both at the first minibatch: PPO's clip is a step
      in the ratio), advantages and returns are the step's.  ``update_rel_l2``
      is ``|dp - dp_ref| / |dp_ref|`` over all parameters,
      ``update_rel_l2_worst`` the same of the worst parameter group but the
      router and ``update_rel_l2_router`` the router's: 0 is the reference's
      update, 1 a state left unchanged.

With ``controls`` in the spec (off in the configuration file: it costs a
second pass of the reference; ``benchmarks/tests`` and the builder turn it
on) the same distances are taken of what a limit has to refuse, each put
through the limits as the program is: the REFERENCE with every product's
operands rounded to ``controls.lower_precision``, the reference with
``controls.faults`` laid on its configuration (no shared expert, weights
unscaled), and the reference's update after the first minibatch alone.

In a traced run the twin also takes the steps such a run takes and leaves
the window's counters in ``harness.traced_counters`` (the runner drops the
step's metrics; the kernels' roofline readers need the rows really routed).

Everything is reduced to numbers on the host and freed before the measured
program is built.  Limits are in the configuration file, with both readings.
"""
from __future__ import annotations

import gc


GROUPS = {
    "in_proj": ("in_proj",), "heads": ("actor_w", "actor_b", "critic_w", "critic_b"),
    "norms": ("attn_norm", "q_a_norm", "kv_a_norm", "ffn_norm", "final_norm"),
    "q_a": ("q_a",), "q_b": ("q_b",), "kv_a": ("kv_a",), "kv_b": ("kv_b",), "o": ("o",),
    "dense_ffn": ("gate", "up", "down"), "router": ("router",),
    "experts": ("experts_gate", "experts_up", "experts_down"),
    "shared": ("shared_gate", "shared_up", "shared_down"),
}


def by_group(tree):
    """{group: the leaves of a reference-shaped tree in it}, all layers together
    (a bias of one element is no leaf to judge an update by)."""
    import jax

    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        for group, names in GROUPS.items():
            if name in names:
                flat.setdefault(group, []).append(leaf)
    return flat


def update_distance(after, ref_after, before):
    """``|d - d_ref| / |d_ref|`` over all parameters, of the worst parameter
    group but the router (and its name), of the router (its gradient moves
    with every flipped choice: judged apart), and of every group, for ``d = after - before`` and ``d_ref =
    ref_after - before``, reference-shaped trees, taken leaf by leaf (the
    trees may lie on the host: a leaf at a time is on the device)."""
    import jax.numpy as jnp

    diff, size = {}, {}
    for g, leaves in by_group(before).items():
        diff[g] = size[g] = 0.0
        for b, a, r in zip(leaves, by_group(after)[g], by_group(ref_after)[g]):
            b, a, r = jnp.asarray(b), jnp.asarray(a), jnp.asarray(r)
            diff[g] += float(jnp.sum(jnp.square(a - r)))
            size[g] += float(jnp.sum(jnp.square(r - b)))
    each = {g: (diff[g] / max(size[g], 1e-60)) ** 0.5 for g in size}
    worst = max((g for g in each if g != "router"), key=each.get)
    return {"update_rel_l2": (sum(diff.values()) / max(sum(size.values()), 1e-60)) ** 0.5,
            "update_rel_l2_worst": each[worst], "update_worst_group": worst,
            "update_rel_l2_router": each.get("router", 0.0), "update_rel_l2_by_group": each}


def over_limits(limits, distances):
    """The names of ``limits`` that ``distances`` has and is over."""
    return sorted(name for name, limit in limits.items()
                  if name in distances and not distances[name] <= float(limit))


def reference(ctx, spec, build, first_steps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from gymfx_tpu.train.common import minibatch_plan

    ref = harness.load_module("checks", spec["reference"])
    trainer = build()
    pcfg = trainer.pcfg
    cfg = {"first_k_dense_replace": 1, "norm_topk_prob": True, "expert_offset": 0,
           "n_shared_experts": 1, **dict(pcfg.policy_kwargs)}
    cfg["experts_held"] = cfg.get("experts_held") or cfg["n_routed_experts"]
    hyper = {"clip_eps": pcfg.clip_eps, "vf_coef": pcfg.vf_coef, "ent_coef": pcfg.ent_coef,
             "lr": pcfg.lr, "max_grad_norm": pcfg.max_grad_norm}
    controls = spec.get("controls") or {}

    # ---- the twin of the timed run: the cell's step program, compiled and
    # warmed up from --seed as the runner will do it again
    state, step, twin = first_steps(trainer)
    counted = tuple(getattr(trainer.policy, "COUNTERS", ()))
    if ctx.trace and counted:
        # a traced run reads its kernels' roofline shares at the rows its window's
        # steps really routed (rooflines/mla_moe_decoder.py::traced_held_share); the
        # runner drops the step's metrics, so the twin takes the steps such a run
        # takes after its warm-up and leaves the window's counters for the readers
        traffic = ctx.cell["traffic"]
        window, read = int(traffic.get("trace_steps", 8)), []
        for _ in range(1 + int(traffic.get("phase_iters", 5)) + window):
            state, metrics = step(state)
            read.append({k: float(metrics[k]) for k in counted})
        harness.traced_counters = {
            k: sum(r[k] for r in read[-window:]) / window for k in counted}
    del state
    gc.collect()

    # ---- one more step of that program, from the state the timed run starts in
    def walk(state):
        inter, out = trainer._rollout_phase(state)
        return inter._replace(params=(), opt_state=()), out

    walk = jax.jit(walk)

    def walked(state):
        inter, out = walk(state)
        return inter._replace(params=state.params, opt_state=state.opt_state), out

    state = trainer.init_state(harness.seed31(ctx.seed))
    after_rollout, (traj, last_value) = walked(state)
    obs = traj["obs"].reshape(-1, *traj["obs"].shape[2:])
    action = traj["action"].reshape(-1)
    block = int(spec["block_decisions"])
    own_routing = jax.jit(lambda p, o: trainer.policy.apply(p, o, routing=True)[2])
    routing = np.concatenate([np.asarray(own_routing(state.params, obs[at:at + block]))
                              for at in range(0, obs.shape[0], block)], axis=1)
    # the step's minibatches: its own fields, plan and permutation; ``sample``
    # numbers the (steps, envs) samples as ``reshape(-1)`` orders them
    advs, returns = trainer._gae(traj, last_value)
    steps, envs = traj["logp"].shape
    fields = {"obs": traj["obs"], "action": traj["action"], "adv": advs, "ret": returns,
              "sample": jnp.arange(steps * envs, dtype=jnp.int32).reshape(steps, envs)}
    n_perm, mb, take = minibatch_plan(
        fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
        horizon=pcfg.horizon, minibatches=pcfg.minibatches)
    perm = jax.random.permutation(
        jax.random.split(after_rollout.rng, pcfg.epochs + 1)[1], n_perm)
    batches = [take(jax.lax.dynamic_slice_in_dim(perm, i * mb, mb))
               for i in range(pcfg.minibatches)]
    got_logp = np.asarray(traj["logp"], np.float32).reshape(-1)
    got_value = np.asarray(traj["value"], np.float32).reshape(-1)
    p0 = jax.tree.map(np.asarray, jax.device_get(state.params))
    state, metrics = step(state)                       # the measured program's own step
    same_rollout = all(
        np.array_equal(a, b, equal_nan=a.dtype.kind == "f")
        for a, b in zip(map(np.asarray, jax.tree.leaves(state.env_states)),
                        map(np.asarray, jax.tree.leaves(after_rollout.env_states))))
    p1 = jax.tree.map(np.asarray, jax.device_get(state.params))
    loss = float(metrics["loss"])
    counters = {k: float(metrics[k]) for k in counted}
    del state, step, after_rollout, traj, fields, advs, returns, metrics, trainer
    gc.collect()

    # ---- the reference, on the same parameters and observations
    def rel(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    def flips(a, b):
        """Share of the (layers, tokens, k) choices of ``a`` that ``b`` did not make."""
        if not a.size:
            return 0.0
        return float(1.0 - (a[..., :, None] == b[..., None, :]).any(-1).mean())

    def rollout_of(cfg, params):
        """(log-probabilities, values, choices) of the reference under ``cfg``."""
        forward = jax.jit(lambda p, o: ref.forward(p, o, cfg, with_routing=True))
        logps, values, chosen = [], [], []
        for at in range(0, obs.shape[0], block):
            logits, value, idx = forward(params, obs[at:at + block])
            logps.append(np.asarray(jnp.take_along_axis(
                jax.nn.log_softmax(logits), action[at:at + block, None], axis=1)[:, 0]))
            values.append(np.asarray(value))
            chosen.append(np.asarray(idx))
        return np.concatenate(logps), np.concatenate(values), np.concatenate(chosen, axis=1)

    def forward_distances(logp, value, chosen, against):
        want_logp, want_value, want_chosen = against
        return {"value_max_abs_diff": float(np.abs(value - want_value).max()),
                "value_rel_l2": rel(value, want_value),
                "logp_max_abs_diff": float(np.abs(logp - want_logp).max()),
                "logp_rel_l2": rel(logp, want_logp),
                "routing_flips": flips(chosen, want_chosen)}

    before, after = ref.from_policy_params(p0, cfg), ref.from_policy_params(p1, cfg)  # host
    del p0, p1
    params = jax.tree.map(jnp.asarray, before)
    plain = rollout_of(cfg, params)
    ref_logp, ref_value, _ = plain
    out = {"kind": "reference_policy", "decisions": int(obs.shape[0]),
           "tokens_per_decision": int(obs.shape[1]), "minibatches": len(batches),
           "minibatch_samples": int(batches[0]["sample"].shape[0]),
           "same_rollout": bool(same_rollout), "value_max_abs": float(np.abs(ref_value).max()),
           **forward_distances(got_logp, got_value, routing, plain), **counters}
    read = {}
    if controls:    # the reference under what a limit has to refuse, on the same parameters
        variants = {name: {**cfg, **over} for name, over in controls.get("faults", {}).items()}
        if controls.get("lower_precision"):
            variants[controls["lower_precision"]] = {
                **cfg, "operand_dtype": controls["lower_precision"]}
        for name, variant in variants.items():
            read[name] = forward_distances(*rollout_of(variant, params), plain)

    # the reference's own update over the step's minibatches (its parameters
    # and one gradient on the device, the moments on the host)
    moments, ref_losses, after_first = ref.adam_init(params), [], None
    for batch in batches:
        batch = dict(batch)
        batch["logp"] = jnp.asarray(ref_logp[np.asarray(batch.pop("sample"))], jnp.float32)
        ref_loss, grads = ref.ppo_loss_and_grads(
            params, batch, cfg, hyper, block=int(spec["block_samples"]))
        params, moments = ref.adam_update(params, grads, moments, hyper)
        del grads
        ref_losses.append(float(ref_loss))
        if controls and after_first is None:
            after_first = jax.tree.map(np.asarray, params)
    del moments, batches
    gc.collect()
    ref_loss = float(np.mean(ref_losses))
    out.update(
        update_distance(after, params, before),
        loss=loss, reference_loss=ref_loss, reference_losses=ref_losses,
        loss_rel_diff=abs(loss - ref_loss) / max(1.0, abs(ref_loss)))
    out["finite"] = bool(np.isfinite(got_logp).all() and np.isfinite(got_value).all()
                         and np.isfinite(loss) and np.isfinite(out["update_rel_l2"]))

    # ---- what the limits have to refuse, each put through them
    if controls:
        part = update_distance(after_first, params, before)
        read["first_minibatch_only"] = {
            **{k: v for k, v in part.items() if isinstance(v, float)},
            "loss_rel_diff": abs(ref_losses[0] - ref_loss) / max(1.0, abs(ref_loss))}
        read["unchanged"] = {"update_rel_l2": 1.0, "update_rel_l2_worst": 1.0,
                             "update_rel_l2_router": 1.0}
        for distances in read.values():
            distances["refused_by"] = over_limits(spec["limits"], distances)
        out["controls"] = read
    del params, before, after, after_first
    gc.collect()
    out["twin"] = twin
    return out


def apart(a, b):
    """Largest absolute difference of two trees of host arrays of one shape."""
    import jax
    import numpy as np

    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        if not np.array_equal(x, y, equal_nan=x.dtype.kind == "f"):
            worst = max(worst, float(np.nanmax(np.abs(
                x.astype(np.float64) - y.astype(np.float64)))))
    return worst


def verdict(spec, ref, got):
    """The timed trainer's first steps are the twin's, and every distance of
    the twin's further step is within the limit the spec gives it."""
    ref = dict(ref)
    twin = ref.pop("twin")
    ref["twin_params_max_abs_diff"] = apart(got["params"], twin["params"])
    ref["twin_loss_max_abs_diff"] = apart([m["loss"] for m in got["metrics"]],
                                          [m["loss"] for m in twin["metrics"]])
    ref["over_limit"] = over_limits(spec["limits"], ref)
    ref["limits_not_read"] = sorted(set(spec["limits"]) - set(ref))
    return ref["finite"] and not ref["over_limit"] and not ref["limits_not_read"], ref
