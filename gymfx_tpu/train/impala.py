"""IMPALA actor-learner with V-trace off-policy correction.

New capability (BASELINE.json config 4: recurrent LSTM policy, IMPALA
async actor-learner over ICI).  Single-program SPMD formulation: the
"actors" are the vmapped env batch stepping with a STALE copy of the
policy (synced every ``sync_every`` learner updates — that staleness is
exactly what V-trace corrects), the learner consumes whole trajectory
segments.  On a pod the same program shards actors over the mesh 'data'
axis and the gradient all-reduce rides ICI; across hosts the mesh
extends over DCN — no parameter server, no gRPC queues.

Unlike the PPO-LSTM shortcut (ppo.py), the learner REPLAYS the segment
through the policy with the stored initial carry, so recurrent credit
assignment is exact over the segment.

V-trace (Espeholt et al. 2018):
  delta_t = rho_t (r_t + gamma_t V(x_{t+1}) - V(x_t))
  vs_t    = V(x_t) + delta_t + gamma_t c_t (vs_{t+1} - V(x_{t+1}))
  pg_adv  = rho_t (r_t + gamma_t vs_{t+1} - V(x_t))
with rho_t = min(rho_bar, pi/mu), c_t = min(c_bar, pi/mu).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from gymfx_tpu.core import env as env_core
from gymfx_tpu.core.runtime import Environment
from gymfx_tpu.parallel.runtime import ShardedRuntime, StatePlan
from gymfx_tpu.resilience.faults import apply_fault_profile_to_market_data
from gymfx_tpu.telemetry import scopes
from gymfx_tpu.train.common import (
    build_train_eval_envs,
    masked_reset,
    picked_logp,
    resolve_collect_dtype,
    resolve_optimizer_state_dtype,
    wire_step_programs,
)
from gymfx_tpu.train.loop import TrainerSpec, train_entry, train_loop
from gymfx_tpu.train.policies import (
    flatten_obs,
    gaussian_entropy,
    is_token_policy,
    policy_kwargs_from,
    make_obs_spec,
    make_trainer_policy,
    normal_logp,
    sample_normal,
    tokens_from_obs,
)


class ImpalaConfig(NamedTuple):
    n_envs: int = 256
    unroll: int = 64
    gamma: float = 0.99
    rho_bar: float = 1.0
    c_bar: float = 1.0
    lr: float = 3e-4
    ent_coef: float = 0.01
    vf_coef: float = 0.5
    max_grad_norm: float = 0.5
    sync_every: int = 4          # actor params refresh period (staleness)
    policy: str = "lstm"
    policy_dtype: Any = jnp.float32
    policy_kwargs: Tuple[Tuple[str, Any], ...] = ()
    # trajectory-obs storage dtype (resolved like PPO's:
    # train/common.resolve_collect_dtype — never wider than policy_dtype)
    collect_dtype: Any = jnp.float32
    # non-finite guard (resilience/guards.py): skip the whole learner
    # update when loss/grads go non-finite and quarantine-reset envs
    # whose segment produced NaN/inf (see train/ppo.py)
    nonfinite_guard: bool = True
    # Adam first-moment storage dtype — resolved through the shared
    # master-weight rule (train/common.resolve_optimizer_state_dtype)
    opt_state_dtype: Any = jnp.float32
    # software-pipelined superstep driver (see train/ppo.PPOConfig);
    # for IMPALA the one-update-stale rollout params are the NATIVE
    # regime — V-trace corrects actor/learner staleness by design
    superstep_overlap: bool = False


def impala_config_from(config: Dict[str, Any]) -> ImpalaConfig:
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        str(config.get("policy_dtype", "float32"))
    ]
    return ImpalaConfig(
        n_envs=int(config.get("num_envs", 256) or 256),
        unroll=int(config.get("impala_unroll", 64)),
        gamma=float(config.get("gamma", 0.99)),
        rho_bar=float(config.get("vtrace_rho_bar", 1.0)),
        c_bar=float(config.get("vtrace_c_bar", 1.0)),
        lr=float(config.get("learning_rate", 3e-4)),
        ent_coef=float(config.get("entropy_coef", 0.01)),
        vf_coef=float(config.get("value_coef", 0.5)),
        max_grad_norm=float(config.get("max_grad_norm", 0.5)),
        sync_every=int(config.get("impala_sync_every", 4)),
        policy=str(config.get("policy") or "lstm"),
        policy_dtype=dt,
        policy_kwargs=tuple(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in policy_kwargs_from(config).items()
        ),
        collect_dtype=resolve_collect_dtype(config, dt),
        nonfinite_guard=bool(config.get("nonfinite_guard", True)),
        opt_state_dtype=resolve_optimizer_state_dtype(config),
        superstep_overlap=bool(config.get("superstep_overlap", False)),
    )


class ImpalaState(NamedTuple):
    learner_params: Any
    actor_params: Any
    opt_state: Any
    env_states: Any
    obs_vec: Any
    policy_carry: Any
    rng: Any
    updates_since_sync: Any  # i32


class ImpalaTrainer:
    ALGO = "impala"

    # shared placement plan (parallel/runtime.ShardedRuntime): learner
    # AND actor params are tensor-shard candidates, the sync counter
    # replicates with opt/rng, the env batch shards over 'data'
    STATE_PLAN = StatePlan(
        params=("learner_params", "actor_params"),
        replicated=("opt_state", "rng", "updates_since_sync"),
        batched=("env_states", "obs_vec", "policy_carry"),
    )

    def __init__(self, env: Environment, icfg: ImpalaConfig, mesh: Optional[Any] = None):
        self.env = env
        self.icfg = icfg
        self.mesh = mesh
        self.runtime = None if mesh is None else ShardedRuntime(mesh)
        # what the host loop reads beside ALGO (train/loop.py)
        self.steps_per_iter = icfg.n_envs * icfg.unroll
        self.nonfinite_guard = icfg.nonfinite_guard
        # V-trace is distribution-agnostic: continuous mode swaps in the
        # Gaussian twin via the shared construction path (only the
        # log-prob and entropy terms change, train/policies.py)
        self._continuous = env.cfg.action_space_mode == "continuous"
        self.policy = make_trainer_policy(
            icfg.policy, continuous=self._continuous,
            dtype=icfg.policy_dtype, kwargs=dict(icfg.policy_kwargs),
            window=env.cfg.window_size,
        )
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(icfg.max_grad_norm),
            optax.adam(icfg.lr, mu_dtype=icfg.opt_state_dtype),
        )
        cfg, params = env.cfg, env.params
        if hasattr(env, "require_resident_data"):
            data = env.require_resident_data(
                "IMPALA training (random-access rollouts)"
            )
        else:
            data = env.data
        self._reset_state, reset_obs = env_core.reset(cfg, params, data)
        self._is_transformer = is_token_policy(icfg.policy)
        self._window = cfg.window_size
        # static obs layout, derived once (see PPOTrainer: the encode
        # hot path must not re-sort keys per call)
        self.obs_spec = make_obs_spec(reset_obs)
        self._reset_vec = self._encode(reset_obs)
        # overlapped supersteps: the update phase owns both param sets
        # (learner gradients, periodic actor sync) and the staleness counter
        wire_step_programs(
            self, overlap=icfg.superstep_overlap,
            learner_fields=(
                "learner_params", "actor_params", "opt_state",
                "updates_since_sync",
            ),
        )

    def _encode(self, obs):
        spec = getattr(self, "obs_spec", None)
        if self._is_transformer:
            return tokens_from_obs(obs, self._window, spec)
        return flatten_obs(obs, spec)

    def _forward(self, params, x, carry):
        if self.icfg.policy == "lstm":
            return self.policy.apply(params, x, carry)
        logits, value = self.policy.apply(params, x)
        return logits, value, carry

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0) -> ImpalaState:
        state = self.init_state_from_key(jax.random.PRNGKey(seed))
        if self.runtime is not None:
            state = self.runtime.place_state(state, self.STATE_PLAN)
        return state

    def init_state_from_key(self, rng) -> ImpalaState:
        """Key-based, unsharded init (traceable; also the resume-template
        shape source)."""
        rng, k = jax.random.split(rng)
        carry0 = self.policy.initial_carry(())
        if self.icfg.policy == "lstm":
            p = self.policy.init(k, self._reset_vec, carry0)
        else:
            p = self.policy.init(k, self._reset_vec)
        n = self.icfg.n_envs
        bcast = lambda x: jnp.broadcast_to(x, (n, *x.shape))  # noqa: E731
        state = ImpalaState(
            learner_params=p,
            # distinct buffers: learner and actor trees are both donated
            # by the jitted step, and XLA rejects donating one buffer twice
            actor_params=jax.tree.map(jnp.copy, p),
            opt_state=self.optimizer.init(p),
            env_states=jax.tree.map(bcast, self._reset_state),
            obs_vec=bcast(self._reset_vec),
            policy_carry=jax.tree.map(bcast, carry0),
            rng=rng,
            updates_since_sync=jnp.zeros((), jnp.int32),
        )
        return state

    # ------------------------------------------------------------------
    def _rollout(self, actor_params, env_states, obs_vec, pcarry, rng,
                 data=None):
        cfg, eparams = self.env.cfg, self.env.params
        # data=None keeps the baked resident tape (bitwise-identical
        # default); an explicit tape (curriculum) is traced and supplies
        # its own in-graph reset (see PPOTrainer._rollout)
        explicit_data = data is not None
        if not explicit_data:
            data = self.env.data
        vstep = jax.vmap(env_core.step, in_axes=(None, None, None, 0, 0))
        vencode = jax.vmap(self._encode)
        fwd = jax.vmap(self._forward, in_axes=(None, 0, 0))
        carry0 = self.policy.initial_carry(())
        if explicit_data:
            reset_state, fresh_obs = env_core.reset(cfg, eparams, data)
            reset_vec = self._encode(fresh_obs)
        else:
            reset_state, reset_vec = self._reset_state, self._reset_vec

        continuous = self._continuous

        def body(carry, _):
            env_states, obs_vec, pcarry, rng = carry
            rng, k = jax.random.split(rng)
            dist, _value, pcarry2 = fwd(actor_params, obs_vec, pcarry)
            if continuous:
                mu, log_std = dist
                action = sample_normal(k, dist)
                logp = normal_logp(action, mu, log_std)
            else:
                logits = dist
                keys = jax.random.split(k, logits.shape[0])
                action = jax.vmap(jax.random.categorical)(keys, logits)
                logp = picked_logp(jax.nn.log_softmax(logits), action)
            env_states2, obs2, reward, done, _ = vstep(
                cfg, eparams, data, env_states, action
            )
            obs_vec2 = vencode(obs2)
            env_states2 = masked_reset(done, reset_state, env_states2)
            obs_vec2 = masked_reset(done, reset_vec, obs_vec2)
            pcarry2 = masked_reset(done, carry0, pcarry2)
            out = dict(
                # obs stored in the resolved collect dtype (never wider
                # than the policy's entry cast — see
                # train/common.resolve_collect_dtype); halves the
                # learner-pass HBM buffer under bf16
                obs=obs_vec.astype(self.icfg.collect_dtype),
                action=action, mu_logp=logp,
                reward=reward.astype(jnp.float32), done=done,
            )
            return (env_states2, obs_vec2, pcarry2, rng), out

        (env_states, obs_vec, pcarry, rng), traj = jax.lax.scan(
            body, (env_states, obs_vec, pcarry, rng), None, length=self.icfg.unroll
        )
        return env_states, obs_vec, pcarry, rng, traj

    def _learner_replay(self, params, traj, init_carry, final_obs_vec):
        """Recompute logits/values over the segment with the LEARNER
        params, threading the true recurrent carry (reset on done)."""
        fwd = jax.vmap(self._forward, in_axes=(None, 0, 0))
        carry0 = self.policy.initial_carry(())

        def body(pcarry, x):
            obs, done = x
            logits, value, pcarry2 = fwd(params, obs, pcarry)
            pcarry2 = masked_reset(done, carry0, pcarry2)
            return pcarry2, (logits, value)

        pcarry, (logits, values) = jax.lax.scan(
            body, init_carry, (traj["obs"], traj["done"])
        )
        _, bootstrap, _ = fwd(params, final_obs_vec, pcarry)
        return logits, values, bootstrap

    def _vtrace(self, values, bootstrap, rewards, dones, rhos):
        g = self.icfg.gamma
        discounts = g * (1.0 - dones.astype(jnp.float32))
        cs = jnp.minimum(self.icfg.c_bar, rhos)
        clipped_rhos = jnp.minimum(self.icfg.rho_bar, rhos)
        values_next = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
        deltas = clipped_rhos * (rewards + discounts * values_next - values)

        def body(acc, x):
            delta, discount, c = x
            acc = delta + discount * c * acc
            return acc, acc

        _, vs_minus_v = jax.lax.scan(
            body,
            jnp.zeros_like(bootstrap),
            (deltas, discounts, cs),
            reverse=True,
        )
        vs = values + vs_minus_v
        vs_next = jnp.concatenate([vs[1:], bootstrap[None]], axis=0)
        pg_adv = clipped_rhos * (rewards + discounts * vs_next - values)
        return jax.lax.stop_gradient(vs), jax.lax.stop_gradient(pg_adv)

    def _loss(self, params, traj, init_carry, final_obs_vec):
        dist, values, bootstrap = self._learner_replay(
            params, traj, init_carry, final_obs_vec
        )
        if self._continuous:
            mu, log_std = dist
            pi_logp = normal_logp(traj["action"], mu, log_std)
            entropy = gaussian_entropy(log_std)
        else:
            logits = dist
            logp_all = jax.nn.log_softmax(logits)
            pi_logp = picked_logp(logp_all, traj["action"])
            entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
        rhos = jnp.exp(pi_logp - traj["mu_logp"])
        vs, pg_adv = self._vtrace(
            values, bootstrap, traj["reward"], traj["done"], rhos
        )
        policy_loss = -jnp.mean(pi_logp * pg_adv)
        value_loss = 0.5 * jnp.mean((vs - values) ** 2)
        total = (
            policy_loss
            + self.icfg.vf_coef * value_loss
            - self.icfg.ent_coef * entropy
        )
        return total, dict(
            policy_loss=policy_loss,
            value_loss=value_loss,
            entropy=entropy,
            mean_rho=rhos.mean(),
        )

    def _rollout_phase(self, state: ImpalaState, data=None):
        """Phase 1: collect one unroll with the (stale) actor params.
        ``rollout_out`` carries the PRE-rollout policy carry alongside
        the segment: the learner replay unrolls the segment from the
        carry the actors STARTED from, not the one they ended with.
        ``_train_step_impl`` is exactly the composition of this and
        :meth:`_update_phase` (bench.py phase attribution; the
        superstep bit-identity tests pin the factoring)."""
        env_states, obs_vec, pcarry, rng, traj = self._rollout(
            state.actor_params, state.env_states, state.obs_vec,
            state.policy_carry, state.rng, data,
        )
        inter = state._replace(
            env_states=env_states, obs_vec=obs_vec, policy_carry=pcarry,
            rng=rng,
        )
        return inter, (traj, state.policy_carry)

    def _update_phase(self, state: ImpalaState, rollout_out, data=None):
        """Phase 2: one V-trace learner update on the collected segment
        (+ guard bookkeeping and the staleness-sync counter)."""
        traj, init_carry = rollout_out
        if data is not None:
            # curriculum quarantine resets come from the ACTIVE tape
            # (XLA CSEs with the rollout's identical reset)
            reset_state, reset_obs = env_core.reset(
                self.env.cfg, self.env.params, data
            )
            reset_vec = self._encode(reset_obs)
        else:
            reset_state, reset_vec = self._reset_state, self._reset_vec
        env_states, obs_vec, pcarry, rng = (
            state.env_states, state.obs_vec, state.policy_carry, state.rng
        )
        (loss, aux), grads = jax.value_and_grad(self._loss, has_aux=True)(
            state.learner_params, traj, init_carry, obs_vec
        )
        updates, new_opt_state = self.optimizer.update(
            grads, state.opt_state, state.learner_params
        )
        new_params = optax.apply_updates(state.learner_params, updates)

        metrics = dict(
            loss=loss,
            mean_reward=traj["reward"].mean(),
            mean_episode_done=traj["done"].mean(),
            **aux,
        )
        if self.icfg.nonfinite_guard:
            from gymfx_tpu.resilience.guards import (
                quarantine_mask,
                select_tree,
                tree_all_finite,
            )

            # IMPALA takes ONE update per step, so the guard is
            # whole-step: a non-finite loss/grad keeps last-good
            # learner params and opt-state bit-for-bit
            ok = jnp.isfinite(loss) & tree_all_finite(grads)
            learner_params = select_tree(
                ok, new_params, state.learner_params
            )
            opt_state = select_tree(ok, new_opt_state, state.opt_state)
            metrics["nonfinite_skips"] = 1.0 - ok.astype(jnp.float32)
            metrics["guard_updates"] = jnp.asarray(1.0, jnp.float32)
            # quarantine envs whose segment or carried state went
            # non-finite (sticky NaN equity, see train/ppo.py)
            poison = quarantine_mask(
                {
                    "reward": traj["reward"],
                    "obs": traj["obs"],
                    "mu_logp": traj["mu_logp"],
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
            pcarry = masked_reset(poison, carry0, pcarry)
            metrics["poisoned_env_resets"] = poison.astype(jnp.float32).sum()
        else:
            learner_params, opt_state = new_params, new_opt_state

        count = state.updates_since_sync + 1
        do_sync = count >= self.icfg.sync_every
        actor_params = jax.tree.map(
            lambda new, old: jnp.where(do_sync, new, old),
            learner_params,
            state.actor_params,
        )
        count = jnp.where(do_sync, 0, count)

        return (
            ImpalaState(
                learner_params, actor_params, opt_state, env_states,
                obs_vec, pcarry, rng, count,
            ),
            metrics,
        )

    def _train_step_impl(self, state: ImpalaState, data=None):
        # phase-named XLA ops for profiler attribution (trace-time
        # metadata only; numerics unchanged) — same scheme as PPO
        with jax.named_scope(scopes.ROLLOUT):
            inter, rollout_out = self._rollout_phase(state, data)
        with jax.named_scope(scopes.UPDATE):
            return self._update_phase(inter, rollout_out, data)

    # ------------------------------------------------------------------
    def train_step(self, state: ImpalaState):
        return self._train_step(state)

    def train_many(self, state: ImpalaState, k: int):
        """``k`` fused train steps in ONE donated dispatch; metrics come
        back stacked on a leading ``(k,)`` axis (see PPOTrainer.train_many)."""
        return self._train_many(state, int(k))

    # -- the host loop's contract (train/loop.py) -----------------------
    def learner_params(self, state: ImpalaState):
        return state.learner_params

    def with_params(self, state: ImpalaState, params) -> ImpalaState:
        # both copies (learner + stale actor), each a buffer of its own
        return state._replace(
            learner_params=params,
            actor_params=jax.tree.map(jnp.copy, params),
        )

    def profiler_info(self) -> Dict[str, int]:
        return dict(n_envs=self.icfg.n_envs, horizon=self.icfg.unroll,
                    update_epochs=1)

    def train(self, total_env_steps: int, seed: int = 0, log_every: int = 0,
              initial_state: Optional[ImpalaState] = None,
              initial_params=None, **hooks):
        """:func:`gymfx_tpu.train.loop.train_loop` on this trainer;
        ``hooks`` are its keyword arguments."""
        return train_loop(
            self, total_env_steps, seed=seed, log_every=log_every,
            initial_state=initial_state, initial_params=initial_params,
            **hooks,
        )


class _EvalShim:
    """Duck-typed adapter exposing the trainer surface evaluate() needs;
    ``env`` overrides the episode dataset (held-out evaluation)."""

    def __init__(self, trainer: ImpalaTrainer, env=None):
        self.env = env if env is not None else trainer.env
        self.policy = trainer.policy
        self._encode = trainer._encode
        self._policy_forward = trainer._forward
        self._greedy_driver = None
        self._continuous = trainer._continuous


def _evaluate(trainer, params, env):
    # greedy eval through PPO's evaluate() machinery, imported here so
    # that the trainers' modules do not import each other
    from gymfx_tpu.train.ppo import evaluate

    return evaluate(_EvalShim(trainer, env=env), params)


SPEC = TrainerSpec(
    build_envs=build_train_eval_envs,
    config_from=impala_config_from,
    trainer_cls=ImpalaTrainer,
    state_cls=ImpalaState,
    checkpoint_metadata=lambda icfg, env: {
        "policy": icfg.policy, "policy_kwargs": dict(icfg.policy_kwargs)},
    evaluate=_evaluate,
    feed_faults=apply_fault_profile_to_market_data,
)


def train_impala_from_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """CLI mode=training entry (train/loop.py ``train_entry``)."""
    return train_entry(config, SPEC)
