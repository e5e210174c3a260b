"""PPO actor-learner, fused with the env scan, sharded over a mesh.

New capability per the north star (BASELINE.json): the reference has no
trainer.  Design:

  * rollout collection IS the env scan: policy apply + env.step run in
    one ``lax.scan`` per train step — no host round trips, no replay
    buffers in host memory;
  * the env batch is data-parallel across the mesh 'data' axis (each
    device steps its shard of envs); wide policy layers may also be
    tensor-sharded across 'model' — placement is owned by the shared
    :class:`~gymfx_tpu.parallel.runtime.ShardedRuntime` plan;
  * gradients are averaged over all envs — under jit with replicated
    params and sharded batch, XLA emits the all-reduce over ICI;
  * auto-reset: terminated envs restart from a fresh reset state inside
    the scan, so training streams continuously over episodes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gymfx_tpu.core import env as env_core
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.parallel.runtime import ShardedRuntime, StatePlan
from gymfx_tpu.resilience.faults import apply_fault_profile_to_market_data
from gymfx_tpu.telemetry import scopes
from gymfx_tpu.train.common import (
    build_train_eval_envs,
    masked_reset,
    minibatch_plan,
    picked_logp,
    resolve_collect_dtype,
    resolve_optimizer_state_dtype,
    validate_minibatch_scheme,
    wire_step_programs,
)
from gymfx_tpu.train.loop import TrainerSpec, train_entry, train_loop
from gymfx_tpu.train.policies import (
    flatten_obs,
    gaussian_entropy,
    is_token_policy,
    make_obs_spec,
    make_trainer_policy,
    policy_kwargs_from,
    normal_logp,
    sample_normal,
    tokens_from_obs,
)


class PPOConfig(NamedTuple):
    n_envs: int = 256
    horizon: int = 128
    epochs: int = 4
    minibatches: int = 4
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    lr: float = 3e-4
    ent_coef: float = 0.01
    vf_coef: float = 0.5
    max_grad_norm: float = 0.5
    policy: str = "mlp"
    policy_dtype: Any = jnp.float32
    policy_kwargs: Tuple[Tuple[str, Any], ...] = ()
    # sample_permute: iid shuffle of all T*N samples per epoch (the
    #   classic PPO treatment; a 2M-row random HBM gather at 32k envs).
    # env_permute: permute ENVS, each minibatch holding whole (T, ...)
    #   trajectories — contiguous large-granularity DMA, the standard
    #   recurrent-PPO sequence minibatching; the product default since
    #   round 6 (held-out parity evidence:
    #   examples/results/minibatch_scheme_parity.json).
    minibatch_scheme: str = "sample_permute"
    # storage dtype for the collected trajectory obs — the (T, N,
    # obs_dim) buffer is the rollout's widest write and the update's
    # widest read.  Resolved in ppo_config_from to the NARROWER of this
    # and policy_dtype (storing wider than the policy's entry cast is
    # pure HBM waste); bf16 with a f32 policy is the lossy opt-in
    # (quality-parity gate: docs/performance.md).  Actions, log-probs,
    # values, advantages stay f32 — PPO ratio numerics untouched.
    collect_dtype: Any = jnp.float32
    # non-finite guard (resilience/guards.py): skip any minibatch update
    # whose loss or grads are non-finite (params/opt-state keep the
    # last-good values bit-for-bit) and quarantine-reset envs whose
    # rollout produced NaN/inf — one poisoned feed bar no longer
    # corrupts the train state irrecoverably
    nonfinite_guard: bool = True
    # Adam first-moment storage dtype (the largest optimizer buffer).
    # bfloat16 halves its HBM footprint/traffic; params and the second
    # moment stay float32 — the master-weight rule, mirrored on
    # resolve_collect_dtype and gated by a learning-parity smoke
    # (tests/test_opt_state_dtype.py).  float32 = bitwise-identical
    # default (optax stores mu in the param dtype either way).
    opt_state_dtype: Any = jnp.float32
    # software-pipelined superstep driver
    # (train/common.make_train_many_overlapped): rollout i+1 issues
    # alongside update i inside train_many dispatches.  Opt-in — see
    # the semantics note on that function.
    superstep_overlap: bool = False
    # rematerialize the policy forward inside the PPO loss (jax.remat):
    # the backward GEMM chain recomputes activations in VMEM instead of
    # staging them through HBM — same math, fewer HBM round trips
    update_remat: bool = False


def ppo_config_from(config: Dict[str, Any]) -> PPOConfig:
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        str(config.get("policy_dtype", "float32"))
    ]
    return PPOConfig(
        n_envs=int(config.get("num_envs", 256) or 256),
        horizon=int(config.get("ppo_horizon", 128)),
        epochs=int(config.get("ppo_epochs", 4)),
        minibatches=int(config.get("ppo_minibatches", 4)),
        gamma=float(config.get("gamma", 0.99)),
        gae_lambda=float(config.get("gae_lambda", 0.95)),
        clip_eps=float(config.get("ppo_clip_eps", 0.2)),
        lr=float(config.get("learning_rate", 3e-4)),
        ent_coef=float(config.get("entropy_coef", 0.01)),
        vf_coef=float(config.get("value_coef", 0.5)),
        max_grad_norm=float(config.get("max_grad_norm", 0.5)),
        policy=str(config.get("policy") or "mlp"),
        policy_dtype=dt,
        policy_kwargs=tuple(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in policy_kwargs_from(config).items()
        ),
        minibatch_scheme=str(
            config.get("ppo_minibatch_scheme", "env_permute")
        ),
        collect_dtype=resolve_collect_dtype(config, dt),
        nonfinite_guard=bool(config.get("nonfinite_guard", True)),
        opt_state_dtype=resolve_optimizer_state_dtype(config),
        superstep_overlap=bool(config.get("superstep_overlap", False)),
        update_remat=bool(config.get("ppo_update_remat", False)),
    )


# one shared definition of the Gaussian distribution helpers
# (train/policies.py); the local alias keeps this module's call sites
_normal_logp = normal_logp


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    env_states: Any      # vmapped EnvState batch
    obs_vec: Any         # (n_envs, obs_dim) policy inputs
    policy_carry: Any    # recurrent carry (or ())
    rng: Any


class PPOTrainer:
    """Builds the jitted train_step for (Environment, PPOConfig)."""

    ALGO = "ppo"

    # shared placement plan (parallel/runtime.ShardedRuntime): params
    # tensor-shard wide matrices over 'model', opt/rng replicate, the
    # env batch shards its leading axis over 'data'
    STATE_PLAN = StatePlan(
        params=("params",),
        replicated=("opt_state", "rng"),
        batched=("env_states", "obs_vec", "policy_carry"),
    )

    def __init__(self, env: Environment, pcfg: PPOConfig, mesh: Optional[Any] = None):
        self.env = env
        self.pcfg = pcfg
        self.mesh = mesh
        self.runtime = None if mesh is None else ShardedRuntime(mesh)
        # what the host loop reads beside ALGO (train/loop.py)
        self.steps_per_iter = pcfg.n_envs * pcfg.horizon
        self.nonfinite_guard = pcfg.nonfinite_guard
        validate_minibatch_scheme(
            pcfg.minibatch_scheme, pcfg.n_envs, pcfg.minibatches,
            horizon=pcfg.horizon,
        )
        self._continuous = env.cfg.action_space_mode == "continuous"
        self.policy = make_trainer_policy(
            pcfg.policy, continuous=self._continuous,
            dtype=pcfg.policy_dtype, kwargs=dict(pcfg.policy_kwargs),
            window=env.cfg.window_size,
        )
        self.optimizer = self._make_optimizer()

        cfg, params = env.cfg, env.params
        if hasattr(env, "require_resident_data"):
            data = env.require_resident_data("PPO training (random-access rollouts)")
        else:
            data = env.data
        self._reset_state, reset_obs = env_core.reset(cfg, params, data)
        self._is_transformer = is_token_policy(pcfg.policy)
        self._window = cfg.window_size
        # static obs layout, derived once per env config: the encode hot
        # path (traced per rollout step, and per request when serving)
        # must not re-sort keys / re-derive shapes every call
        self.obs_spec = make_obs_spec(reset_obs)
        self._reset_vec = self._encode(reset_obs)
        self.obs_dim = self._reset_vec.shape

        self._random_start = bool(env.config.get("random_episode_start", False))
        wire_step_programs(self, overlap=pcfg.superstep_overlap)

    # ------------------------------------------------------------------
    def _make_optimizer(self):
        return optax.chain(
            optax.clip_by_global_norm(self.pcfg.max_grad_norm),
            optax.adam(self.pcfg.lr, mu_dtype=self.pcfg.opt_state_dtype),
        )

    def _encode(self, obs: Dict[str, Any]):
        spec = getattr(self, "obs_spec", None)
        if self._is_transformer:
            return tokens_from_obs(obs, self._window, spec)
        return flatten_obs(obs, spec)

    def init_state(self, seed: int = 0) -> TrainState:
        state = self.init_state_from_key(jax.random.PRNGKey(seed))
        if self.runtime is not None:
            state = self.runtime.place_state(state, self.STATE_PLAN)
        return state

    def init_state_from_key(self, rng) -> TrainState:
        """Key-based init (traceable — PBT vmaps this over a population)."""
        rng, k_init = jax.random.split(rng)
        carry0 = self.policy.initial_carry(())
        n = self.pcfg.n_envs
        if self._random_start:
            # the first episodes start at random offsets too, as every
            # later one does (_rollout): the batch covers the tape, and its
            # windows hold bars and not padding, from the first step on
            rng, k0 = jax.random.split(rng)
            t0s = jax.random.randint(k0, (n,), 0, max(1, self.env.cfg.n_bars - 2))
            env_states, first_obs = jax.vmap(
                env_core.reset_at, in_axes=(None, None, None, 0)
            )(self.env.cfg, self.env.params, self.env.data, t0s)
            obs_vec = jax.vmap(self._encode)(first_obs)
            # every leaf a buffer of its own (reset hands one array out
            # under two fields; the donated step takes each once)
            env_states = jax.tree.map(jnp.array, env_states)
        else:
            env_states = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n, *x.shape)), self._reset_state
            )
            obs_vec = jnp.broadcast_to(self._reset_vec, (n, *self._reset_vec.shape))
        sample = self._reset_vec
        if getattr(self.policy, "takes_batch", False):
            # a policy that takes the batch is initialised on a batch like
            # the ones it will meet (an expert layer balances its choice
            # bias on it)
            rng, k_walk = jax.random.split(rng)
            sample = self._first_batch(k_walk, env_states)
        if self.pcfg.policy == "lstm":
            p = self.policy.init(k_init, sample, carry0)
        else:
            p = self.policy.init(k_init, sample)
        opt_state = self.optimizer.init(p)

        pcarry = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n, *x.shape)), carry0
        )
        return TrainState(p, opt_state, env_states, obs_vec, pcarry, rng)

    def _first_batch(self, rng, env_states):
        """One horizon of uniformly random actions from ``env_states``,
        every ``minibatches``-th step's observations: a minibatch's worth
        of windows with the agent states a rollout meets (a position held
        is a feature of every token of its window; the reset's are all
        flat).  The states walked through are dropped."""
        cfg, eparams, data = self.env.cfg, self.env.params, self.env.data
        vstep = jax.vmap(env_core.step, in_axes=(None, None, None, 0, 0))

        def body(states, key):
            action = jax.random.randint(
                key, (self.pcfg.n_envs,), 0, self.policy.n_actions)
            states, obs, *_ = vstep(cfg, eparams, data, states, action)
            return states, jax.vmap(self._encode)(obs)

        _, walked = jax.lax.scan(
            body, env_states, jax.random.split(rng, self.pcfg.horizon))
        kept = walked[self.pcfg.minibatches - 1::self.pcfg.minibatches]
        return kept.reshape(-1, *kept.shape[2:])

    # ------------------------------------------------------------------
    def _policy_forward(self, params, obs_vec, pcarry, counters=False):
        """(dist, value, carry); with ``counters`` also the counters a policy
        keeps of its own layers (``policy.COUNTERS``: an expert layer's load)."""
        if self.pcfg.policy == "lstm":
            return self.policy.apply(params, obs_vec, pcarry)
        if counters:
            logits, value, counted = self.policy.apply(params, obs_vec, counters=True)
            return logits, value, pcarry, jax.lax.stop_gradient(counted)
        logits, value = self.policy.apply(params, obs_vec)
        return logits, value, pcarry

    def _batch_forward(self):
        """The policy over the env batch: vmapped per env, or called on the
        batch as it is where the module takes one itself (an expert layer
        sorts the tokens of the whole batch)."""
        if getattr(self.policy, "takes_batch", False):
            return self._policy_forward
        return jax.vmap(self._policy_forward, in_axes=(None, 0, 0))

    def _rollout(self, params, env_states, obs_vec, pcarry, rng, data=None):
        cfg, eparams = self.env.cfg, self.env.params
        # data=None (every non-curriculum path) bakes the env's resident
        # tape into the trace exactly as before — bitwise identical; an
        # explicit tape (curriculum) is a traced argument, so the reset
        # state/obs must be derived from IT in-graph
        explicit_data = data is not None
        if not explicit_data:
            data = self.env.data
        vstep = jax.vmap(env_core.step, in_axes=(None, None, None, 0, 0))
        vencode = jax.vmap(self._encode)
        fwd = self._batch_forward()
        carry0 = self.policy.initial_carry(())
        if self._random_start:
            # a per-env bank of fresh episodes at random offsets, drawn
            # once per rollout (per-step random resets would reintroduce
            # the vmapped window gather the streaming carries eliminated)
            rng, k0 = jax.random.split(rng)
            t0s = jax.random.randint(
                k0, (self.pcfg.n_envs,), 0, max(1, cfg.n_bars - 2)
            )
            reset_state, fresh_obs = jax.vmap(
                env_core.reset_at, in_axes=(None, None, None, 0)
            )(cfg, eparams, data, t0s)
            reset_vec = vencode(fresh_obs)
        elif explicit_data:
            reset_state, fresh_obs = env_core.reset(cfg, eparams, data)
            reset_vec = self._encode(fresh_obs)
        else:
            reset_state = self._reset_state
            reset_vec = self._reset_vec

        continuous = self._continuous

        def body(carry, _):
            env_states, obs_vec, pcarry, rng = carry
            with jax.named_scope(scopes.POLICY_ACT):
                rng, k = jax.random.split(rng)
                dist, value, pcarry2 = fwd(params, obs_vec, pcarry)
                if continuous:
                    mu, log_std = dist
                    action = sample_normal(k, dist)
                    logp = _normal_logp(action, mu, log_std)
                else:
                    logits = dist
                    keys = jax.random.split(k, logits.shape[0])
                    action = jax.vmap(jax.random.categorical)(keys, logits)
                    logp = picked_logp(jax.nn.log_softmax(logits), action)
            # env_core.step plants env_step/{tape_read,dynamics,obs}
            env_states2, obs2, reward, done, _ = vstep(
                cfg, eparams, data, env_states, action
            )
            with jax.named_scope(scopes.join(scopes.ENV_STEP, scopes.OBS)):
                obs_vec2 = vencode(obs2)
            with jax.named_scope(scopes.AUTO_RESET):
                # auto-reset terminated envs (fresh episode, fresh carry)
                env_states2 = masked_reset(done, reset_state, env_states2)
                obs_vec2 = masked_reset(done, reset_vec, obs_vec2)
                pcarry2 = masked_reset(done, carry0, pcarry2)
                out = dict(
                    # store obs in the resolved collect dtype (never wider
                    # than the policy's entry cast — resolve_collect_dtype):
                    # the (T*N, obs_dim) buffer is the rollout's widest
                    # write and the update's widest read, and it halves
                    # under bf16
                    obs=obs_vec.astype(self.pcfg.collect_dtype),
                    action=action, logp=logp, value=value,
                    reward=reward.astype(jnp.float32), done=done,
                    # the carry that ENTERED this step — replayed during
                    # the minibatch passes so recurrent policies see
                    # exactly the state they acted with (stored-state
                    # recurrent replay)
                    pcarry=pcarry,
                )
            return (env_states2, obs_vec2, pcarry2, rng), out

        (env_states, obs_vec, pcarry, rng), traj = jax.lax.scan(
            body, (env_states, obs_vec, pcarry, rng), None,
            length=self.pcfg.horizon,
        )
        # bootstrap value for the final obs
        with jax.named_scope(scopes.POLICY_ACT):
            logits, last_value, _ = fwd(params, obs_vec, pcarry)
        return env_states, obs_vec, pcarry, rng, traj, last_value

    def _gae(self, traj, last_value):
        g, lam = self.pcfg.gamma, self.pcfg.gae_lambda

        def body(carry, x):
            adv_next, v_next = carry
            reward, value, done = x
            nonterm = 1.0 - done.astype(jnp.float32)
            delta = reward + g * v_next * nonterm - value
            adv = delta + g * lam * nonterm * adv_next
            return (adv, value), adv

        (_, _), advs = jax.lax.scan(
            body,
            (jnp.zeros_like(last_value), last_value),
            (traj["reward"], traj["value"], traj["done"]),
            reverse=True,
        )
        returns = advs + traj["value"]
        return advs, returns

    def _loss(self, params, batch):
        fwd = self._batch_forward()
        # counters a policy keeps of its own layers ride out of the loss
        # beside its three parts
        counted = bool(getattr(self.policy, "COUNTERS", ()))
        if counted:
            fwd = partial(self._policy_forward, counters=True)
        if self.pcfg.update_remat:
            # recompute the forward activations inside the backward pass
            # (same ops, same order — no numeric change) instead of
            # staging every minibatch activation through HBM; on TPU the
            # whole loss GEMM chain then runs VMEM-resident
            fwd = jax.remat(fwd)
        # inside value_and_grad the scope reads jvp(policy_forward) on the
        # forward pass and transpose(jvp(policy_forward)) on the backward
        with jax.named_scope(scopes.POLICY_FORWARD):
            dist, value, _, *rest = fwd(params, batch["obs"], batch["pcarry"])
        counters = rest[0] if counted else {}
        if self._continuous:
            mu, log_std = dist
            logp = _normal_logp(batch["action"], mu, log_std)
            entropy = gaussian_entropy(log_std)
        else:
            logits = dist
            logp_all = jax.nn.log_softmax(logits)
            logp = picked_logp(logp_all, batch["action"])
            entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
        ratio = jnp.exp(logp - batch["logp"])
        adv = batch["adv"]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        clip_eps, ent_coef = self._loss_hyper()
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv
        policy_loss = -jnp.mean(jnp.minimum(unclipped, clipped))
        value_loss = 0.5 * jnp.mean((value - batch["ret"]) ** 2)
        total = (
            policy_loss
            + self.pcfg.vf_coef * value_loss
            - ent_coef * entropy
        )
        return total, dict(
            policy_loss=policy_loss, value_loss=value_loss, entropy=entropy,
            **counters,
        )

    def _loss_hyper(self):
        """(clip_eps, ent_coef) used by the loss — static config values
        here; the PBT cores override them with per-member TRACED values
        read from opt_state.hyperparams so a vmapped population explores
        them independently (train/pbt.py)."""
        return self.pcfg.clip_eps, self.pcfg.ent_coef

    def _rollout_phase(self, state: TrainState, data=None):
        """Phase 1 of the train step: collect one horizon of experience.
        Returns the post-rollout carry state (params/opt untouched) and
        the rollout products the update consumes.  ``_train_step_impl``
        is EXACTLY the composition of this and :meth:`_update_phase` —
        the split exists so bench.py can time each phase off its own
        donated executable (rollout_ms / update_ms), and the superstep
        bit-identity tests (tests/test_superstep.py) pin the factoring."""
        env_states, obs_vec, pcarry_end, rng, traj, last_value = self._rollout(
            state.params, state.env_states, state.obs_vec, state.policy_carry,
            state.rng, data,
        )
        inter = TrainState(
            state.params, state.opt_state, env_states, obs_vec, pcarry_end, rng
        )
        return inter, (traj, last_value)

    def _update_phase(self, state: TrainState, rollout_out, data=None):
        """Phase 2 of the train step: GAE + minibatched epochs + guard
        bookkeeping on an already-collected trajectory."""
        pcfg = self.pcfg
        if data is not None:
            # curriculum: quarantine resets must come from the ACTIVE
            # tape, not the baked tape-0 reset (XLA CSEs this with the
            # rollout's identical reset when both phases share a trace)
            reset_state, reset_obs = env_core.reset(
                self.env.cfg, self.env.params, data
            )
            reset_vec = self._encode(reset_obs)
        else:
            reset_state, reset_vec = self._reset_state, self._reset_vec
        traj, last_value = rollout_out
        env_states, obs_vec, pcarry_end, rng = (
            state.env_states, state.obs_vec, state.policy_carry, state.rng
        )
        with jax.named_scope(scopes.GAE):
            advs, returns = self._gae(traj, last_value)

        # Stored-state recurrent replay: each step replays with the carry
        # it was collected under (R2D2-style stored state), so at the
        # first epoch the replayed log-probs equal the stored ones
        # exactly (ratio == 1) — no zero-carry approximation.  Carries
        # go stale across epochs as params move, the standard stored-
        # state trade-off; IMPALA re-unrolls from scratch instead
        # (train/impala.py).
        fields = {
            "obs": traj["obs"],
            "action": traj["action"],
            "logp": traj["logp"],
            "adv": advs,
            "ret": returns,
            "pcarry": traj["pcarry"],
        }
        n_perm, mb, take = minibatch_plan(
            fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
            horizon=pcfg.horizon, minibatches=pcfg.minibatches,
        )
        params, opt_state = state.params, state.opt_state
        guard = pcfg.nonfinite_guard
        from gymfx_tpu.resilience.guards import (
            quarantine_mask,
            select_tree,
            tree_all_finite,
        )

        def epoch_body(carry, k):
            params, opt_state = carry
            with jax.named_scope(scopes.MINIBATCH_TAKE):
                perm = jax.random.permutation(k, n_perm)

            def mb_body(carry, i):
                params, opt_state = carry
                with jax.named_scope(scopes.MINIBATCH_TAKE):
                    idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                batch = take(idx)  # minibatch_plan plants the same scope
                with jax.named_scope(scopes.LOSS):
                    (loss, aux), grads = jax.value_and_grad(
                        self._loss, has_aux=True
                    )(params, batch)
                with jax.named_scope(scopes.OPTIMIZER):
                    updates, new_opt_state = self.optimizer.update(
                        grads, opt_state, params
                    )
                    new_params = optax.apply_updates(params, updates)
                    if guard:
                        # non-finite loss/grads: keep last-good params and
                        # opt-state bit-for-bit (one NaN minibatch would
                        # otherwise poison the Adam moments forever)
                        ok = jnp.isfinite(loss) & tree_all_finite(grads)
                        params = select_tree(ok, new_params, params)
                        opt_state = select_tree(ok, new_opt_state, opt_state)
                    else:
                        ok = jnp.asarray(True)
                        params, opt_state = new_params, new_opt_state
                return (params, opt_state), (loss, aux, ok)

            (params, opt_state), (losses, auxes, oks) = jax.lax.scan(
                mb_body, (params, opt_state), jnp.arange(pcfg.minibatches)
            )
            return (params, opt_state), (losses, auxes, oks)

        rng, *ks = jax.random.split(rng, pcfg.epochs + 1)
        (params, opt_state), (losses, auxes, oks) = jax.lax.scan(
            epoch_body, (params, opt_state), jnp.stack(ks)
        )

        with jax.named_scope(scopes.GUARD):
            if guard:
                okf = oks.astype(jnp.float32)
                n_ok = okf.sum()

                def mmean(x):
                    # mean over SURVIVING minibatches only; NaN iff every
                    # update this step was skipped (an honest signal — a
                    # finite number here would hide total divergence)
                    safe = jnp.where(jnp.isfinite(x), x, 0.0)
                    return jnp.where(
                        n_ok > 0, (safe * okf).sum() / jnp.maximum(n_ok, 1.0),
                        jnp.nan,
                    )

                metrics = dict(
                    loss=mmean(losses),
                    policy_loss=mmean(auxes["policy_loss"]),
                    value_loss=mmean(auxes["value_loss"]),
                    entropy=mmean(auxes["entropy"]),
                    **{k: mmean(auxes[k]) for k in getattr(self.policy, "COUNTERS", ())},
                    mean_reward=traj["reward"].mean(),
                    mean_episode_done=traj["done"].mean(),
                    nonfinite_skips=(1.0 - okf).sum(),
                    guard_updates=jnp.asarray(
                        float(pcfg.epochs * pcfg.minibatches), jnp.float32
                    ),
                )
                # quarantine: envs whose rollout or carried state went
                # non-finite restart from a fresh episode — NaN equity would
                # otherwise stick and re-poison every later rollout
                poison = quarantine_mask(
                    {
                        "reward": traj["reward"],
                        "obs": traj["obs"],
                        "value": traj["value"],
                        "logp": traj["logp"],
                    },
                    env_axis=1,
                ) | quarantine_mask(
                    # NaN-only for carried state: env peak/min/max trackers
                    # hold ±inf sentinels by design (core/types.py)
                    {"obs_vec": obs_vec, "env_states": env_states},
                    env_axis=0, mode="nan",
                )
                carry0 = self.policy.initial_carry(())
                env_states = masked_reset(poison, reset_state, env_states)
                obs_vec = masked_reset(poison, reset_vec, obs_vec)
                pcarry_end = masked_reset(poison, carry0, pcarry_end)
                metrics["poisoned_env_resets"] = poison.astype(jnp.float32).sum()
            else:
                metrics = dict(
                    loss=losses.mean(),
                    policy_loss=auxes["policy_loss"].mean(),
                    value_loss=auxes["value_loss"].mean(),
                    entropy=auxes["entropy"].mean(),
                    **{k: auxes[k].mean() for k in getattr(self.policy, "COUNTERS", ())},
                    mean_reward=traj["reward"].mean(),
                    mean_episode_done=traj["done"].mean(),
                )
        new_state = TrainState(
            params, opt_state, env_states, obs_vec, pcarry_end, rng
        )
        return new_state, metrics

    def _train_step_impl(self, state: TrainState, data=None):
        # named_scope labels the XLA ops by phase (trace-time metadata
        # only — the compiled program and numerics are unchanged), so a
        # profiler capture attributes device time to rollout vs update
        with jax.named_scope(scopes.ROLLOUT):
            inter, rollout_out = self._rollout_phase(state, data)
        with jax.named_scope(scopes.UPDATE):
            return self._update_phase(inter, rollout_out, data)

    # ------------------------------------------------------------------
    def train_step(self, state: TrainState):
        return self._train_step(state)

    def train_many(self, state: TrainState, k: int):
        """``k`` fused train steps in ONE donated dispatch (lax.scan over
        the per-step impl).  Returns ``(state, metrics)`` with every
        metric stacked on a leading ``(k,)`` axis — accumulated on
        device, fetched by the caller once per superstep."""
        return self._train_many(state, int(k))

    # -- the host loop's contract (train/loop.py) -----------------------
    def learner_params(self, state: TrainState):
        return state.params

    def with_params(self, state: TrainState, params) -> TrainState:
        return state._replace(params=params)

    def profiler_info(self) -> Dict[str, int]:
        return dict(n_envs=self.pcfg.n_envs, horizon=self.pcfg.horizon,
                    update_epochs=self.pcfg.epochs)

    def train(self, total_env_steps: int, seed: int = 0, log_every: int = 0,
              initial_params=None, initial_state: Optional[TrainState] = None,
              **hooks):
        """Run PPO for ~total_env_steps: :func:`gymfx_tpu.train.loop.
        train_loop` on this trainer; ``hooks`` are its keyword arguments
        (checkpoints, preemption drill, supersteps, telemetry)."""
        return train_loop(
            self, total_env_steps, seed=seed, log_every=log_every,
            initial_params=initial_params, initial_state=initial_state,
            **hooks,
        )


# ---------------------------------------------------------------------------
def greedy_policy_driver(trainer: PPOTrainer):
    """Deterministic (argmax) eval driver.  Cached per trainer: the
    Driver is a static jit argument, so the policy params travel in the
    (traced) driver carry — repeated evals with new weights reuse the
    compiled episode scan."""
    if getattr(trainer, "_greedy_driver", None) is not None:
        return trainer._greedy_driver
    from gymfx_tpu.core.rollout import Driver

    def act(carry, obs, i, key):
        params, pcarry = carry
        vec = trainer._encode(obs)
        dist, _value, pcarry = trainer._policy_forward(params, vec, pcarry)
        if trainer._continuous:
            mu, _log_std = dist
            return mu, (params, pcarry)  # deterministic: the mean action
        return jnp.argmax(dist, axis=-1).astype(jnp.int32), (params, pcarry)

    trainer._greedy_driver = Driver(init=lambda: (), act=act)
    return trainer._greedy_driver


def evaluate(trainer: PPOTrainer, params, steps: Optional[int] = None, seed: int = 0):
    """Greedy-policy episode -> reference-style metrics summary."""
    from gymfx_tpu.core.rollout import rollout_chunked
    from gymfx_tpu.metrics import compute_analyzers, summarize_trading

    env = trainer.env
    steps = int(steps or env.cfg.n_bars - 1)
    driver = greedy_policy_driver(trainer)
    state, out = rollout_chunked(
        env.cfg, env.params, env.data, driver, steps, jax.random.PRNGKey(seed),
        driver_carry=(params, trainer.policy.initial_carry(())),
    )
    equity = np.asarray(out["equity_delta"], np.float64) + float(
        env.params.initial_cash
    )
    done = np.asarray(out["done"])
    ts = env.dataset.timestamps.iloc[1 : steps + 1]
    analyzers = compute_analyzers(equity=equity, done=done, state=state, timestamps=ts)
    final_eq = float(equity[int(np.argmax(done))] if done.any() else equity[-1])
    summary = summarize_trading(
        initial_cash=float(env.params.initial_cash),
        final_equity=final_eq,
        analyzers=analyzers,
        config=env.config,
    )
    tf_hours = env.dataset.timeframe_hours or (1.0 / 60.0)
    summary["sharpe_ratio_steps"] = _step_sharpe(equity, tf_hours)
    return summary


def _step_sharpe(equity: np.ndarray, timeframe_hours: float) -> Optional[float]:
    """Per-step Sharpe annualized by the bar timeframe (252 trading
    days x 24h / bar hours steps per year)."""
    rets = np.diff(equity) / equity[:-1]
    if rets.size < 2 or rets.std(ddof=1) == 0:
        return None
    steps_per_year = 252.0 * 24.0 / max(timeframe_hours, 1e-9)
    return float(rets.mean() / rets.std(ddof=1) * np.sqrt(steps_per_year))


def eval_policy_from_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """CLI driver_mode=policy: load the checkpointed policy and run a
    greedy evaluation episode (shared skeleton:
    train/common.py eval_checkpointed_policy — honors the checkpoint's
    recorded architecture and the out-of-sample keys)."""
    from gymfx_tpu.train.common import eval_checkpointed_policy

    def resolve(meta, cfg):
        if not cfg.get("policy") and meta.get("policy"):
            cfg["policy"] = meta["policy"]
            cfg.setdefault("policy_kwargs", meta.get("policy_kwargs") or {})

    return eval_checkpointed_policy(
        config,
        build_envs=build_train_eval_envs,
        make_trainer=lambda env, cfg: PPOTrainer(env, ppo_config_from(cfg)),
        evaluate_fn=lambda tr, params, steps: evaluate(tr, params, steps=steps),
        resolve_policy=resolve,
    )


SPEC = TrainerSpec(
    build_envs=build_train_eval_envs,
    config_from=ppo_config_from,
    trainer_cls=PPOTrainer,
    state_cls=TrainState,
    checkpoint_metadata=lambda pcfg, env: {
        "policy": pcfg.policy, "policy_kwargs": dict(pcfg.policy_kwargs)},
    evaluate=lambda trainer, params, env: evaluate(
        trainer if env is None else PPOTrainer(env, trainer.pcfg), params),
    feed_faults=apply_fault_profile_to_market_data,
)


def train_from_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """CLI mode=training entry (train/loop.py ``train_entry``)."""
    return train_entry(config, SPEC)
