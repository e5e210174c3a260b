"""PPO over the multi-pair portfolio environment (BASELINE config 5:
multi-pair portfolio, Transformer policy, pod scale).

Differences from the single-pair trainer (train/ppo.py):
  * actions are per-pair vectors (I,) in {0,1,2,3}\\{3} — the policy
    emits independent categorical heads, one per instrument, and the
    joint log-prob is the sum of per-pair log-probs;
  * observations come from the portfolio obs dict ((window, I) price
    blocks); the Transformer treats bars as tokens with per-pair
    channels, the MLP flattens.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from gymfx_tpu.core import portfolio as P
from gymfx_tpu.parallel.runtime import ShardedRuntime, StatePlan
from gymfx_tpu.train.common import (
    build_portfolio_train_eval_envs,
    masked_reset,
    minibatch_plan,
    picked_logp,
    resolve_collect_dtype,
    resolve_optimizer_state_dtype,
    validate_minibatch_scheme,
    wire_step_programs,
)
from gymfx_tpu.train.loop import TrainerSpec, train_entry, train_loop
from gymfx_tpu.train.policies import RingTransformerEncoder, is_token_policy


def _per_pair_heads(pooled, n_pairs: int):
    """Shared actor-critic head: per-pair categorical logits (I, 3) +
    scalar value — one definition for all portfolio policies."""
    logits = nn.Dense(n_pairs * 3, dtype=jnp.float32)(pooled)
    value = nn.Dense(1, dtype=jnp.float32)(pooled)
    return logits.reshape(*logits.shape[:-1], n_pairs, 3), jnp.squeeze(value, -1)


class PortfolioMLPPolicy(nn.Module):
    n_pairs: int
    hidden: Tuple[int, ...] = (256, 256, 256)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.tanh(nn.Dense(width, dtype=self.dtype)(x))
        return _per_pair_heads(x, self.n_pairs)


class PortfolioTransformerPolicy(nn.Module):
    """Attention over bars; tokens carry all pairs' features."""

    n_pairs: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        x = nn.Dense(self.d_model, dtype=self.dtype)(tokens.astype(self.dtype))
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (tokens.shape[-2], self.d_model), jnp.float32,
        )
        x = x + pos.astype(self.dtype)
        for _ in range(self.n_layers):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.n_heads, dtype=self.dtype
            )(y, y)
            x = x + y
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.Dense(self.d_model * 4, dtype=self.dtype)(y)
            y = nn.gelu(y)
            y = nn.Dense(self.d_model, dtype=self.dtype)(y)
            x = x + y
        pooled = jnp.mean(nn.LayerNorm(dtype=self.dtype)(x), axis=-2)
        return _per_pair_heads(pooled, self.n_pairs)


class PortfolioRingTransformerPolicy(nn.Module):
    """Portfolio actor-critic over the shared RingTransformerEncoder:
    attention over bars (tokens carry all pairs' features) that can run
    sequence-parallel ring attention over a 'seq' mesh axis — BASELINE
    config 5's portfolio + Transformer + pod-scale combination.  Use
    train.policies.seq_sharded_forward for the sharded mode; parameters
    are identical in both modes."""

    n_pairs: int
    window: int = 32
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    dtype: Any = jnp.float32
    seq_axis: Any = None
    seq_shards: int = 1
    sp_backend: str = "ring"

    @nn.compact
    def __call__(self, tokens):
        pooled = RingTransformerEncoder(
            window=self.window, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, dtype=self.dtype,
            seq_axis=self.seq_axis, seq_shards=self.seq_shards,
            sp_backend=self.sp_backend,
        )(tokens)
        return _per_pair_heads(pooled, self.n_pairs)


class PortfolioPPOConfig(NamedTuple):
    n_envs: int = 64
    horizon: int = 64
    epochs: int = 2
    minibatches: int = 4
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    lr: float = 3e-4
    ent_coef: float = 0.01
    vf_coef: float = 0.5
    max_grad_norm: float = 0.5
    policy: str = "mlp"  # mlp | transformer | transformer_ring | transformer_ulysses
    # sample_permute | env_permute — the same schemes as the single-pair
    # trainer (train/ppo.py PPOConfig.minibatch_scheme)
    minibatch_scheme: str = "sample_permute"
    # policy compute dtype (heads stay f32 like the single-pair policies)
    policy_dtype: Any = jnp.float32
    # trajectory-obs storage dtype — THE widest buffers in the repo
    # ((T, N, window*pairs*features) portfolio obs); resolved like the
    # single-pair trainers (train/common.resolve_collect_dtype)
    collect_dtype: Any = jnp.float32
    # Adam first-moment dtype (train/common.resolve_optimizer_state_dtype):
    # only mu narrows — nu feeds the 1/sqrt(nu) rescale and stays f32
    # alongside the master weights
    opt_state_dtype: Any = jnp.float32


def portfolio_config_from(config: Dict[str, Any]) -> PortfolioPPOConfig:
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        str(config.get("policy_dtype", "float32"))
    ]
    return PortfolioPPOConfig(
        n_envs=int(config.get("num_envs", 64) or 64),
        horizon=int(config.get("ppo_horizon", 64)),
        epochs=int(config.get("ppo_epochs", 2)),
        minibatches=int(config.get("ppo_minibatches", 4)),
        lr=float(config.get("learning_rate", 3e-4)),
        policy=str(config.get("policy") or "mlp"),
        minibatch_scheme=str(
            config.get("ppo_minibatch_scheme", "env_permute")
        ),
        policy_dtype=dt,
        collect_dtype=resolve_collect_dtype(config, dt),
        opt_state_dtype=resolve_optimizer_state_dtype(config),
    )


class PortfolioTrainState(NamedTuple):
    params: Any
    opt_state: Any
    env_states: Any
    obs_vec: Any
    rng: Any


def _encode_mlp(obs: Dict[str, Any]):
    return jnp.concatenate(
        [jnp.ravel(obs[k]).astype(jnp.float32) for k in sorted(obs)], axis=0
    )


def _encode_tokens(obs: Dict[str, Any], window: int):
    cols = []
    for k in sorted(obs):
        v = obs[k]
        # portfolio window blocks are 2-D (window, I); 1-D blocks are
        # per-pair/scalar state broadcast along the window (shape tests
        # alone would misfire when n_pairs == window)
        if v.ndim >= 2 and v.shape[0] == window:
            cols.append(v.reshape(window, -1).astype(jnp.float32))
        else:
            flat = jnp.ravel(v).astype(jnp.float32)
            cols.append(jnp.broadcast_to(flat[None, :], (window, flat.shape[0])))
    return jnp.concatenate(cols, axis=-1)


class PortfolioPPOTrainer:
    ALGO = "portfolio_ppo"

    # shared placement plan (parallel/runtime.ShardedRuntime); the
    # portfolio state has no recurrent carry — otherwise identical to PPO
    STATE_PLAN = StatePlan(
        params=("params",),
        replicated=("opt_state", "rng"),
        batched=("env_states", "obs_vec"),
    )

    def __init__(self, env: P.PortfolioEnvironment, pcfg: PortfolioPPOConfig,
                 mesh: Optional[Any] = None):
        self.env = env
        self.pcfg = pcfg
        self.mesh = mesh
        self.runtime = None if mesh is None else ShardedRuntime(mesh)
        # what the host loop reads beside ALGO (train/loop.py); the step
        # carries no non-finite guard, so no skip watchdog runs
        self.steps_per_iter = pcfg.n_envs * pcfg.horizon
        self.nonfinite_guard = False
        validate_minibatch_scheme(
            pcfg.minibatch_scheme, pcfg.n_envs, pcfg.minibatches,
            horizon=pcfg.horizon,
        )
        n_pairs = env.cfg.n_pairs
        if pcfg.policy == "transformer":
            self.policy = PortfolioTransformerPolicy(
                n_pairs=n_pairs, dtype=pcfg.policy_dtype
            )
        elif pcfg.policy in ("transformer_ring", "transformer_ulysses"):
            self.policy = PortfolioRingTransformerPolicy(
                n_pairs=n_pairs, window=env.cfg.window_size,
                dtype=pcfg.policy_dtype,
                sp_backend="ulysses" if pcfg.policy == "transformer_ulysses"
                else "ring",
            )
        elif pcfg.policy == "mlp":
            self.policy = PortfolioMLPPolicy(
                n_pairs=n_pairs, dtype=pcfg.policy_dtype
            )
        else:
            raise ValueError(
                f"portfolio trainer supports policy "
                f"mlp|transformer|transformer_ring|transformer_ulysses, "
                f"got {pcfg.policy!r}"
            )
        self.optimizer = self._make_optimizer()
        self._reset_state, reset_obs = P.reset(env.cfg, env.params, env.data)
        self._window = env.cfg.window_size
        self._is_transformer = is_token_policy(pcfg.policy)
        self._reset_vec = self._encode(reset_obs)
        # no K-step program: the portfolio trains one step a dispatch
        wire_step_programs(self, supersteps=False)

    def _encode(self, obs):
        if self._is_transformer:
            return _encode_tokens(obs, self._window)
        return _encode_mlp(obs)

    # ------------------------------------------------------------------
    def _make_optimizer(self):
        return optax.chain(
            optax.clip_by_global_norm(self.pcfg.max_grad_norm),
            optax.adam(self.pcfg.lr, mu_dtype=self.pcfg.opt_state_dtype),
        )

    def init_state(self, seed: int = 0) -> PortfolioTrainState:
        state = self.init_state_from_key(jax.random.PRNGKey(seed))
        if self.runtime is not None:
            state = self.runtime.place_state(state, self.STATE_PLAN)
        return state

    def init_state_from_key(self, rng) -> PortfolioTrainState:
        rng, k = jax.random.split(rng)
        params = self.policy.init(k, self._reset_vec)
        n = self.pcfg.n_envs
        bcast = lambda x: jnp.broadcast_to(x, (n, *x.shape))  # noqa: E731
        return PortfolioTrainState(
            params=params,
            opt_state=self.optimizer.init(params),
            env_states=jax.tree.map(bcast, self._reset_state),
            obs_vec=bcast(self._reset_vec),
            rng=rng,
        )

    def _forward(self, params, x):
        return self.policy.apply(params, x)

    def _rollout(self, params, env_states, obs_vec, rng, data=None):
        cfg, eparams = self.env.cfg, self.env.params
        explicit_data = data is not None
        if not explicit_data:
            data = self.env.data
        vstep = jax.vmap(P.step, in_axes=(None, None, None, 0, 0))
        vencode = jax.vmap(self._encode)
        fwd = jax.vmap(self._forward, in_axes=(None, 0))
        if explicit_data:
            # curriculum tape: episode restarts must come from the ACTIVE
            # tape, so the reset rides the trace instead of the baked
            # (tape-0) constants
            reset_state, fresh_obs = P.reset(cfg, eparams, data)
            reset_vec = self._encode(fresh_obs)
        else:
            reset_state, reset_vec = self._reset_state, self._reset_vec

        def body(carry, _):
            env_states, obs_vec, rng = carry
            rng, k = jax.random.split(rng)
            logits, value = fwd(params, obs_vec)          # (B, I, 3), (B,)
            actions = jax.random.categorical(k, logits)   # (B, I)
            logp = picked_logp(
                jax.nn.log_softmax(logits), actions
            ).sum(axis=-1)                                # joint logp
            env_states2, obs2, reward, done, _info = vstep(
                cfg, eparams, data, env_states, actions
            )
            obs_vec2 = vencode(obs2)
            env_states2 = masked_reset(done, reset_state, env_states2)
            obs_vec2 = masked_reset(done, reset_vec, obs_vec2)
            out = dict(
                # the (T, N, window*pairs*features) obs block is the
                # repo's widest trajectory buffer — stored in the
                # resolved collect dtype (train/common.resolve_collect_dtype;
                # bf16 halves its write+read HBM traffic); actions/
                # log-probs/values stay f32 so ratio numerics hold
                obs=obs_vec.astype(self.pcfg.collect_dtype),
                action=actions, logp=logp, value=value,
                reward=reward.astype(jnp.float32), done=done)
            return (env_states2, obs_vec2, rng), out

        (env_states, obs_vec, rng), traj = jax.lax.scan(
            body, (env_states, obs_vec, rng), None, length=self.pcfg.horizon
        )
        _, bootstrap = jax.vmap(self._forward, in_axes=(None, 0))(params, obs_vec)
        return env_states, obs_vec, rng, traj, bootstrap

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
            body, (jnp.zeros_like(last_value), last_value),
            (traj["reward"], traj["value"], traj["done"]), reverse=True,
        )
        return advs, advs + traj["value"]

    def _loss(self, params, batch):
        logits, value = jax.vmap(self._forward, in_axes=(None, 0))(
            params, batch["obs"]
        )
        logp_all = jax.nn.log_softmax(logits)
        logp = picked_logp(logp_all, batch["action"]).sum(axis=-1)
        ratio = jnp.exp(logp - batch["logp"])
        adv = batch["adv"]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        clip_eps, ent_coef = self._loss_hyper()
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv
        policy_loss = -jnp.mean(jnp.minimum(unclipped, clipped))
        value_loss = 0.5 * jnp.mean((value - batch["ret"]) ** 2)
        entropy = -jnp.mean(
            jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).sum(axis=-1)
        )
        total = (
            policy_loss + self.pcfg.vf_coef * value_loss
            - ent_coef * entropy
        )
        return total, dict(policy_loss=policy_loss, value_loss=value_loss,
                           entropy=entropy)

    def _loss_hyper(self):
        """(clip_eps, ent_coef) for the loss — static here; the PBT core
        overrides with per-member traced values (train/pbt.py)."""
        return self.pcfg.clip_eps, self.pcfg.ent_coef

    def _rollout_phase(self, state: PortfolioTrainState, data=None):
        """Phase 1 of the train step (see train/ppo.py _rollout_phase:
        the split exists for bench phase attribution and is pinned to
        compose bitwise into ``_train_step_impl``)."""
        env_states, obs_vec, rng, traj, bootstrap = self._rollout(
            state.params, state.env_states, state.obs_vec, state.rng, data
        )
        inter = PortfolioTrainState(
            state.params, state.opt_state, env_states, obs_vec, rng
        )
        return inter, (traj, bootstrap)

    def _update_phase(self, state: PortfolioTrainState, rollout_out):
        """Phase 2: GAE + minibatched epochs on a collected trajectory."""
        pcfg = self.pcfg
        traj, bootstrap = rollout_out
        env_states, obs_vec, rng = state.env_states, state.obs_vec, state.rng
        advs, returns = self._gae(traj, bootstrap)
        fields = {
            "obs": traj["obs"],
            "action": traj["action"],
            "logp": traj["logp"],
            "adv": advs,
            "ret": returns,
        }
        n_perm, mb, take = minibatch_plan(
            fields, scheme=pcfg.minibatch_scheme, n_envs=pcfg.n_envs,
            horizon=pcfg.horizon, minibatches=pcfg.minibatches,
        )
        params, opt_state = state.params, state.opt_state

        def epoch_body(carry, k):
            params, opt_state = carry
            perm = jax.random.permutation(k, n_perm)

            def mb_body(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                batch = take(idx)
                (loss, aux), grads = jax.value_and_grad(
                    self._loss, has_aux=True
                )(params, batch)
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
                return (params, opt_state), (loss, aux)

            (params, opt_state), outs = jax.lax.scan(
                mb_body, (params, opt_state), jnp.arange(pcfg.minibatches)
            )
            return (params, opt_state), outs

        rng, *ks = jax.random.split(rng, pcfg.epochs + 1)
        (params, opt_state), (losses, auxes) = jax.lax.scan(
            epoch_body, (params, opt_state), jnp.stack(ks)
        )
        metrics = dict(
            loss=losses.mean(),
            policy_loss=auxes["policy_loss"].mean(),
            value_loss=auxes["value_loss"].mean(),
            entropy=auxes["entropy"].mean(),
            mean_reward=traj["reward"].mean(),
        )
        return PortfolioTrainState(params, opt_state, env_states, obs_vec, rng), metrics

    def _train_step_impl(self, state: PortfolioTrainState, data=None):
        inter, rollout_out = self._rollout_phase(state, data)
        return self._update_phase(inter, rollout_out)

    def train_step(self, state):
        return self._train_step(state)

    # -- the host loop's contract (train/loop.py) -----------------------
    def learner_params(self, state: PortfolioTrainState):
        return state.params

    def with_params(self, state: PortfolioTrainState,
                    params) -> PortfolioTrainState:
        return state._replace(params=params)

    def profiler_info(self) -> Dict[str, int]:
        return dict(n_envs=self.pcfg.n_envs, horizon=self.pcfg.horizon,
                    update_epochs=self.pcfg.epochs)

    def train(self, total_env_steps: int, seed: int = 0,
              initial_params=None, initial_state=None, **hooks):
        """:func:`gymfx_tpu.train.loop.train_loop` on this trainer;
        ``hooks`` are its keyword arguments.  The same resume contract
        as the single-pair trainers: ``initial_state`` continues a
        checkpointed run exactly, ``initial_params`` warm-starts."""
        return train_loop(
            self, total_env_steps, seed=seed,
            initial_params=initial_params, initial_state=initial_state,
            **hooks,
        )


def evaluate(trainer: "PortfolioPPOTrainer", params,
             steps: Optional[int] = None, chunk: int = 128) -> Dict[str, Any]:
    """Greedy (per-pair argmax) portfolio episode -> reference-style
    trading metrics on the ACCOUNT ledger, trade statistics pooled over
    pairs.  Chunked scan (fixed-size jitted chunks) so long episodes
    compile once — the portfolio twin of train/ppo.py evaluate."""
    import math
    import types

    from gymfx_tpu.metrics import compute_analyzers, summarize_trading
    from gymfx_tpu.train.ppo import _step_sharpe

    env = trainer.env
    cfg, eparams, data = env.cfg, env.params, env.data
    steps = int(steps or cfg.n_bars - 1)
    state0, obs0 = P.reset(cfg, eparams, data)
    vec0 = trainer._encode(obs0)

    @jax.jit
    def run_chunk(params, st, vec):
        def body(carry, _):
            st, vec = carry
            logits, _v = trainer._forward(params, vec)
            action = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            st2, obs2, _r, done, info = P.step(cfg, eparams, data, st, action)
            return (st2, trainer._encode(obs2)), (info["equity"], done)

        (st, vec), outs = jax.lax.scan(body, (st, vec), None, length=chunk)
        return st, vec, outs

    state, vec = state0, vec0
    eqs, dones = [], []
    for _ in range(max(1, math.ceil(steps / chunk))):
        state, vec, (eq, dn) = run_chunk(params, state, vec)
        eqs.append(np.asarray(eq, np.float64))
        dones.append(np.asarray(dn, bool))
    equity = np.concatenate(eqs)[:steps]
    done = np.concatenate(dones)[:steps]

    pairs, acct = jax.device_get((state.pairs, state.acct))
    agg = types.SimpleNamespace(
        trade_count=int(np.sum(pairs.trade_count)),
        trades_won=int(np.sum(pairs.trades_won)),
        trades_lost=int(np.sum(pairs.trades_lost)),
        trade_pnl_sum=float(np.sum(pairs.trade_pnl_sum)),
        trade_pnl_sumsq=float(np.sum(pairs.trade_pnl_sumsq)),
        max_drawdown_pct=float(acct.max_drawdown_pct),
        max_drawdown_money=float(acct.max_drawdown_money),
    )
    ts = env.timestamps[1 : steps + 1]
    analyzers = compute_analyzers(equity=equity, done=done, state=agg,
                                  timestamps=ts)
    final_eq = float(equity[int(np.argmax(done))] if done.any() else equity[-1])
    summary = summarize_trading(
        initial_cash=float(eparams.acct.initial_cash),
        final_equity=final_eq,
        analyzers=analyzers,
        config=env.config,
    )
    tf_hours = env.timeframe_hours or (1.0 / 60.0)
    summary["sharpe_ratio_steps"] = _step_sharpe(equity, tf_hours)
    summary["pairs"] = list(env.pairs)
    return summary


def eval_portfolio_policy_from_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """CLI ``driver_mode=policy`` with ``portfolio_files``: greedy
    evaluation of a checkpointed portfolio policy via the shared
    skeleton (train/common.py eval_checkpointed_policy), with the
    pair-set checked against the checkpoint (positional heads)."""
    from gymfx_tpu.train.common import eval_checkpointed_policy

    def resolve(meta, cfg):
        stored = str(meta.get("policy") or "")
        if not cfg.get("policy") and stored.startswith("portfolio_"):
            cfg["policy"] = stored[len("portfolio_"):]

    def validate(meta, env):
        if meta.get("pairs") and list(meta["pairs"]) != list(env.pairs):
            raise ValueError(
                f"checkpoint was trained on pairs {meta['pairs']}, config "
                f"loads {env.pairs} — the per-pair heads are positional"
            )

    return eval_checkpointed_policy(
        config,
        build_envs=build_portfolio_train_eval_envs,
        make_trainer=lambda env, cfg: PortfolioPPOTrainer(
            env, PortfolioPPOConfig(policy=str(cfg.get("policy") or "mlp"))
        ),
        evaluate_fn=lambda tr, params, steps: evaluate(tr, params, steps=steps),
        resolve_policy=resolve,
        validate=validate,
    )


SPEC = TrainerSpec(
    build_envs=build_portfolio_train_eval_envs,
    config_from=portfolio_config_from,
    trainer_cls=PortfolioPPOTrainer,
    state_cls=PortfolioTrainState,
    # composite checkpoints: the FULL train state for exact resume plus
    # a standalone params item for cheap evaluation restores
    checkpoint_metadata=lambda pcfg, env: {
        "policy": f"portfolio_{pcfg.policy}", "pairs": env.pairs},
    evaluate=lambda trainer, params, env: evaluate(
        trainer if env is None else PortfolioPPOTrainer(env, trainer.pcfg),
        params),
    summary_extra=lambda env: {
        "mode": "training", "trainer": "portfolio_ppo", "pairs": env.pairs},
)


def train_portfolio_from_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """CLI mode=training entry (train/loop.py ``train_entry``)."""
    return train_entry(config, SPEC)
