"""Shared training helpers."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from gymfx_tpu.telemetry import scopes


def make_train_many(step_impl):
    """Superstep driver: jitted ``train_many(state, k)`` running ``k``
    fused train steps in ONE donated dispatch.

    ``step_impl(state) -> (state, metrics)`` is the same per-step impl
    the trainers jit as ``train_step``; here it becomes the body of a
    ``lax.scan``, so the Python interpreter pays one dispatch (and the
    caller one metrics fetch) per K steps instead of per step.  Metrics
    come back stacked on a leading ``(k,)`` axis — accumulated on
    device, including the resilience guard counters, and fetched once
    per superstep.

    ``k`` is static: each distinct K compiles once (the trainers use one
    K for the whole run plus at most one remainder).
    """

    def impl(state, k: int):
        def body(s, _):
            return step_impl(s)

        return jax.lax.scan(body, state, None, length=k)

    return jax.jit(impl, static_argnums=1, donate_argnums=0)


def make_train_many_with_data(step_impl):
    """Curriculum variant of :func:`make_train_many`: jitted
    ``train_many(state, data, k)`` where the MarketData tape is a traced
    argument instead of a closure constant, so ONE compiled superstep
    serves every tape of the registry (all tapes share static shapes).
    Only the state is donated — the tape is owned by the sampler and
    reused across supersteps."""

    def impl(state, data, k: int):
        def body(s, _):
            return step_impl(s, data)

        return jax.lax.scan(body, state, None, length=k)

    return jax.jit(impl, static_argnums=2, donate_argnums=0)


def make_train_many_overlapped(
    rollout_phase, update_phase, learner_fields=("params", "opt_state"),
):
    """Software-pipelined superstep driver: jitted ``train_many(state,
    k)`` where iteration ``i+1``'s rollout is ISSUED in the same scan
    body as iteration ``i``'s update, so the XLA scheduler can overlap
    the rollout's small-op env chain with the update's GEMM chain
    instead of running the two phases back to back.

    Shape: prologue rollout, then ``k - 1`` pipelined bodies
    {rollout(i+1) on pre-update params || update(i)}, then the epilogue
    update — the same number of rollouts and updates as the sequential
    driver.  ``learner_fields`` names the state fields the update owns
    (params/opt state/actor-sync counters); the body grafts them from
    the update's result onto the already-issued rollout's carry.

    Semantics (why this is OPT-IN, ``superstep_overlap`` in
    config/defaults.py):

      * rollouts act on params ONE update stale — the standard
        actor-learner pipelining trade (IMPALA makes it explicit with
        V-trace; for PPO the stored log-probs stay self-consistent, the
        data is just one policy version old);
      * the guard's quarantine env resets (and any other update-side
        edits to env/obs/carry state) are dropped inside a dispatch,
        because the next rollout already consumed the pre-update state;
      * the rollout/update RNG streams are pre-split per body so the
        two concurrent phases never share a key.

    ``k=1`` has no pipelined body — prologue + epilogue compose exactly
    the sequential train step, which the parity test pins bitwise
    (tests/test_overlap_superstep.py).  Metrics return stacked on a
    leading ``(k,)`` axis like :func:`make_train_many`.
    """

    def merge(rolled, updated):
        return rolled._replace(
            **{f: getattr(updated, f) for f in learner_fields}
        )

    def impl(state, k: int):
        inter, ro = rollout_phase(state)

        def body(carry, _):
            inter, ro = carry
            r_next, r_upd = jax.random.split(inter.rng)
            inter2, ro2 = rollout_phase(inter._replace(rng=r_next))
            updated, metrics = update_phase(inter._replace(rng=r_upd), ro)
            return (merge(inter2, updated), ro2), metrics

        if k > 1:
            (inter, ro), stacked = jax.lax.scan(
                body, (inter, ro), None, length=k - 1
            )
        final, last = update_phase(inter, ro)
        if k > 1:
            metrics = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]]), stacked, last
            )
        else:
            metrics = jax.tree.map(lambda x: x[None], last)
        return final, metrics

    return jax.jit(impl, static_argnums=1, donate_argnums=0)


def wire_step_programs(trainer, *, supersteps: bool = True,
                       overlap: bool = False,
                       learner_fields=("params", "opt_state")) -> None:
    """The tail of every step-program trainer's ``__init__``: jit
    ``trainer._train_step_impl`` as the donated ``_train_step`` and, with
    ``supersteps``, as the body of the K-step ``_train_many`` (the
    pipelined driver under ``overlap``, whose update phase owns
    ``learner_fields``).

    feed=curriculum: the sampler swaps whole tapes at superstep
    boundaries, so the tape becomes a TRACED argument of
    ``_train_step_data`` / ``_train_many_data``: one executable serves
    every tape, and only the state is donated, never the shared tape."""
    impl = trainer._train_step_impl
    trainer._train_step = jax.jit(impl, donate_argnums=0)
    trainer.curriculum = getattr(trainer.env, "curriculum", None)
    if trainer.curriculum is not None and overlap:
        raise ValueError(
            "feed=curriculum cannot be combined with "
            "superstep_overlap: the pipelined driver issues rollout "
            "i+1 before update i, so a tape swap inside the dispatch "
            "would feed half a superstep from the wrong tape"
        )
    if trainer.curriculum is not None:
        trainer._train_step_data = jax.jit(impl, donate_argnums=0)
        if supersteps:
            trainer._train_many_data = make_train_many_with_data(impl)
    if not supersteps:
        return
    if overlap:
        trainer._train_many = make_train_many_overlapped(
            trainer._rollout_phase, trainer._update_phase,
            learner_fields=learner_fields,
        )
    else:
        trainer._train_many = make_train_many(impl)


def resolve_collect_dtype(config: Dict[str, Any], policy_dtype) -> Any:
    """Trajectory-obs storage dtype: the narrower of
    ``rollout_collect_dtype`` and the policy compute dtype.  Every
    policy casts its input to its compute dtype at entry, so storing
    wider than that cast is pure HBM waste (bf16 policies keep the
    historical bf16 storage under the f32 default), while
    ``rollout_collect_dtype: bfloat16`` with a f32 policy is the lossy
    opt-in documented in docs/performance.md."""
    cd = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        str(config.get("rollout_collect_dtype", "float32"))
    ]
    if policy_dtype == jnp.bfloat16 or cd == jnp.bfloat16:
        return jnp.bfloat16
    return cd


def resolve_optimizer_state_dtype(config: Dict[str, Any]) -> Any:
    """Adam first-moment storage dtype from the config knob.  The
    master-weight rule is fixed, not configurable: only ``mu`` narrows
    (it is a smoothed gradient — bf16's ~3 decimal digits track it),
    while params and ``nu`` stay float32 (``nu`` feeds the 1/sqrt
    rescale where bf16 quantization would modulate the effective lr).
    Mirrors :func:`resolve_collect_dtype`'s one-definition discipline —
    every trainer resolves through here."""
    dt = str(config.get("optimizer_state_dtype", "float32")).lower()
    if dt not in ("float32", "bfloat16"):
        raise ValueError(
            f"optimizer_state_dtype must be 'float32' or 'bfloat16', "
            f"got {dt!r}"
        )
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dt]


def build_train_eval_envs(config: Dict[str, Any]) -> Tuple[Any, Optional[Any]]:
    """(train_env, eval_env-or-None) honoring the out-of-sample keys.

    ``eval_data_file``   evaluate on a separate dataset file;
    ``eval_split``       hold out the LAST fraction of bars (chronological
                         split — the only sound one for market series:
                         a random split would leak future bars into
                         training).
    Without either, eval_env is None and evaluation is in-sample (the
    round-2 behavior, now labeled as such in the summary).
    """
    from gymfx_tpu.core.runtime import Environment

    eval_file = config.get("eval_data_file")
    split = config.get("eval_split")
    feed = str(config.get("feed") or "replay").lower()
    if eval_file and split:
        raise ValueError("set either eval_data_file or eval_split, not both")
    if feed == "curriculum" and split:
        raise ValueError(
            "feed=curriculum cannot hold out via eval_split (which tape "
            "would be cut?); name a held-out tape with eval_data_file"
        )
    if eval_file:
        eval_config = dict(config)
        eval_config["input_data_file"] = str(eval_file)
        if feed in ("scengen", "curriculum"):
            # train-on-synthetic / eval-on-real: the named eval file is
            # by definition a replayed tape
            eval_config["feed"] = "replay"
            eval_config.pop("tapes", None)
        return Environment(config), Environment(eval_config)
    if split:
        frac = float(split)
        if not 0.0 < frac < 1.0:
            raise ValueError(f"eval_split must be in (0, 1), got {split!r}")
        min_bars = int(config.get("window_size", 32)) + 2

        def check(cut: int, n_all: int) -> None:
            if cut < min_bars or n_all - cut < min_bars:
                raise ValueError(
                    f"eval_split={frac} leaves too few bars (train {cut}, "
                    f"eval {n_all - cut}; both need >= {min_bars})"
                )

        if feed == "scengen":
            # generate ONCE, then split chronologically — the same
            # no-leakage cut as the replay path, and both halves come
            # from one seeded tape (regenerating per half would desync
            # the overlay processes at the cut)
            from gymfx_tpu.scengen.feed import ScenGenDataset

            full = ScenGenDataset(config)
            n_all = len(full)
            cut = n_all - int(n_all * frac)
            check(cut, n_all)
            return (
                Environment(config, dataset=full.sliced(slice(0, cut))),
                Environment(config, dataset=full.sliced(slice(cut, None))),
            )
        from gymfx_tpu.data.feed import MarketDataset, load_dataframe

        df = load_dataframe(config)
        cut = len(df) - int(len(df) * frac)
        check(cut, len(df))
        train_env = Environment(
            config, dataset=MarketDataset(df.iloc[:cut], config)
        )
        eval_env = Environment(
            config, dataset=MarketDataset(df.iloc[cut:], config)
        )
        return train_env, eval_env
    return Environment(config), None


def build_portfolio_train_eval_envs(config: Dict[str, Any]) -> Tuple[Any, Optional[Any]]:
    """(train_env, eval_env-or-None) for the multi-pair portfolio env.

    ``eval_portfolio_files``  evaluate on a separate per-pair file map;
    ``eval_split``            hold out the LAST fraction of the ALIGNED
                              bars (chronological, applied after the
                              cross-pair timestamp join so no pair
                              leaks future bars into training).
    ``eval_data_file`` is rejected loudly: a single file cannot describe
    a multi-pair book.
    """
    from gymfx_tpu.core.portfolio import PortfolioEnvironment

    if config.get("eval_data_file"):
        raise ValueError(
            "portfolio trainers hold out via eval_split or "
            "eval_portfolio_files (a per-pair file map); eval_data_file "
            "is single-pair only"
        )
    eval_files = config.get("eval_portfolio_files")
    split = config.get("eval_split")
    if eval_files and split:
        raise ValueError("set either eval_portfolio_files or eval_split, not both")
    if eval_files:
        eval_config = dict(config)
        eval_config["portfolio_files"] = dict(eval_files)
        eval_config.pop("eval_portfolio_files", None)
        train_env = PortfolioEnvironment(config)
        eval_env = PortfolioEnvironment(eval_config)
        # the policy's per-pair heads/obs channels are POSITIONAL: a
        # different pair set or ordering would silently evaluate the
        # wrong instruments on the wrong heads
        if list(eval_env.pairs) != list(train_env.pairs):
            raise ValueError(
                "eval_portfolio_files must list the same pairs in the "
                f"same order as portfolio_files (train {train_env.pairs}, "
                f"eval {eval_env.pairs})"
            )
        return train_env, eval_env
    if split:
        frac = float(split)
        return (
            PortfolioEnvironment(config, split=("train", frac)),
            PortfolioEnvironment(config, split=("eval", frac)),
        )
    return PortfolioEnvironment(config), None


def labeled_eval_summary(make_summary, train_env, eval_env) -> Dict[str, Any]:
    """One definition of the out-of-sample summary shape for every
    trainer: ``make_summary(env_or_None)`` runs a greedy evaluation on
    the given env (None = the training env)."""
    if eval_env is None:
        summary = make_summary(None)
        summary["eval_scope"] = "in_sample"
        return summary
    summary = make_summary(eval_env)
    summary["eval_scope"] = "held_out"
    summary["eval_bars"] = eval_env.n_bars
    summary["train_bars"] = train_env.n_bars
    summary["in_sample"] = make_summary(None)
    return summary


def eval_checkpointed_policy(
    config: Dict[str, Any],
    *,
    build_envs,
    make_trainer,
    evaluate_fn,
    resolve_policy=None,
    validate=None,
) -> Dict[str, Any]:
    """The one ``driver_mode=policy`` skeleton shared by the single-pair
    and portfolio paths: checkpoint-dir guard, metadata honor
    (``resolve_policy(meta, config)`` mutates the config copy),
    train/eval env build, template-validated params restore, greedy
    evaluation, and the labeled summary keys.  ``validate(meta, env)``
    rejects checkpoint/config mismatches loudly (e.g. portfolio pair
    sets)."""
    import jax

    ckpt_dir = config.get("checkpoint_dir")
    if not ckpt_dir:
        raise ValueError("driver_mode=policy requires checkpoint_dir")
    from gymfx_tpu.train.checkpoint import load_params, read_metadata

    meta = read_metadata(str(ckpt_dir))
    config = dict(config)
    # the minibatch scheme shapes only the UPDATE pass, which never runs
    # in inference — pin the scheme that is valid for ANY env count so
    # the env_permute training default (config/defaults.py) cannot
    # reject a single-env eval trainer at construction
    config["ppo_minibatch_scheme"] = "sample_permute"
    if resolve_policy is not None:
        resolve_policy(meta, config)
    train_env, eval_env = build_envs(config)
    env = eval_env if eval_env is not None else train_env
    if validate is not None:
        validate(meta, env)
    trainer = make_trainer(env, config)
    # template-validated restore: an architecture mismatch fails loudly
    # at load time, not as an opaque shape error inside the episode scan
    template = jax.eval_shape(
        lambda k: trainer.init_state_from_key(k).params, jax.random.PRNGKey(0)
    )
    params, step = load_params(str(ckpt_dir), template=template)
    summary = evaluate_fn(trainer, params, config.get("steps"))
    summary["checkpoint_step"] = step
    summary["eval_scope"] = "held_out" if eval_env is not None else "in_sample"
    summary["mode"] = "inference"
    return summary


def validate_minibatch_scheme(scheme: str, n_envs: int, minibatches: int,
                              *, horizon: Optional[int] = None) -> None:
    """Construction-time validation shared by the PPO trainers."""
    if scheme not in ("sample_permute", "env_permute"):
        raise ValueError(
            "ppo_minibatch_scheme must be 'sample_permute' or "
            f"'env_permute', got {scheme!r}"
        )
    if scheme == "env_permute" and n_envs % minibatches:
        raise ValueError(
            f"env_permute needs num_envs ({n_envs}) divisible by "
            f"ppo_minibatches ({minibatches})"
        )
    if scheme == "sample_permute" and horizon is not None:
        # minibatch_plan slices the permutation into minibatches chunks
        # of floor(T*N / minibatches) — a non-zero remainder of samples
        # is silently never trained on each epoch.  Mirror the
        # env_permute divisibility check as a warning (the drop is a
        # per-epoch random subset, so it biases coverage, not
        # correctness).
        total = int(horizon) * int(n_envs)
        dropped = total % int(minibatches)
        if dropped:
            import warnings

            warnings.warn(
                f"sample_permute drops {dropped} of {total} samples per "
                f"epoch (horizon*num_envs={total} not divisible by "
                f"ppo_minibatches={minibatches}); pick sizes where "
                "horizon*num_envs % minibatches == 0 to train on every "
                "sample",
                stacklevel=2,
            )


def resolve_minibatch_scheme(config, n_envs: int, minibatches: int) -> None:
    """From-config entry-point resolution of the env_permute default
    (config/defaults.py): when the requested scheme is env_permute but
    num_envs < ppo_minibatches — a shape where whole-trajectory
    minibatches CANNOT exist (e.g. the single-env inference default) —
    degrade to sample_permute with a warning instead of refusing to
    train.  Fixable mismatches (num_envs >= minibatches but not
    divisible) still raise at trainer construction
    (:func:`validate_minibatch_scheme`): those have a right answer the
    user should pick.  Mutates ``config`` in place."""
    scheme = str(config.get("ppo_minibatch_scheme", "env_permute"))
    if scheme == "env_permute" and int(n_envs) < int(minibatches):
        import warnings

        warnings.warn(
            f"ppo_minibatch_scheme=env_permute needs num_envs "
            f"({n_envs}) >= ppo_minibatches ({minibatches}); falling "
            "back to sample_permute for this run — raise num_envs to a "
            "multiple of ppo_minibatches to use trajectory minibatches",
            stacklevel=2,
        )
        config["ppo_minibatch_scheme"] = "sample_permute"


def minibatch_plan(fields, *, scheme: str, n_envs: int, horizon: int,
                   minibatches: int):
    """One definition of the PPO update's minibatching schemes, shared
    by the single-pair and portfolio trainers: returns
    ``(n_perm, mb, take)`` where a per-epoch permutation of
    ``n_perm`` indices is sliced into ``minibatches`` chunks of ``mb``
    indices each, and ``take(idx)`` materializes one flat minibatch
    from the (T, N, ...) ``fields``.

      sample_permute  classic iid shuffle of all T*N samples;
      env_permute     permute ENVS, minibatches gather whole (T, ...)
                      trajectories — contiguous DMA, the wide-batch
                      HBM fix (VERDICT r4 #4) and the standard
                      recurrent sequence-minibatch treatment.

    The re-layout here and every ``take`` carry the ``minibatch_take``
    scope (telemetry/scopes.py).
    """
    if scheme == "env_permute":
        with jax.named_scope(scopes.MINIBATCH_TAKE):
            source = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), fields)
        mb = n_envs // minibatches

        def take(idx):
            with jax.named_scope(scopes.MINIBATCH_TAKE):
                return jax.tree.map(
                    lambda x: x[idx].reshape(mb * horizon, *x.shape[2:]),
                    source,
                )

        return n_envs, mb, take

    n_total = horizon * n_envs
    with jax.named_scope(scopes.MINIBATCH_TAKE):
        source = jax.tree.map(
            lambda x: x.reshape(n_total, *x.shape[2:]), fields
        )

    def take(idx):
        with jax.named_scope(scopes.MINIBATCH_TAKE):
            return jax.tree.map(lambda x: x[idx], source)

    return n_total, n_total // minibatches, take


def picked_logp(logp_all, action):
    """Log-probability of the discrete ``action`` along the last axis of
    ``logp_all``, as a compare and a sum over the few actions there are:
    no gather (a TPU gather costs per index: PERF.md section 6), and a
    ``where`` rather than a product with a one-hot, so that a ``-inf``
    on an action NOT chosen leaves value and gradient finite.  The one
    pick of the trainers (ppo, impala, portfolio_ppo).

    ``action`` is an integer array of ``logp_all``'s leading shape with
    values in ``[0, n_actions)``: it comes from
    ``jax.random.categorical`` or from the stored trajectory.  Outside
    that range the result is 0, where ``take_along_axis`` gives NaN.
    """
    hit = action[..., None] == jnp.arange(
        logp_all.shape[-1], dtype=action.dtype
    )
    return jnp.sum(jnp.where(hit, logp_all, 0.0), axis=-1)


def masked_reset(done, fresh_tree, cur_tree):
    """Where ``done`` (batch bool), replace each leaf of ``cur_tree``
    with the (broadcast) corresponding leaf of ``fresh_tree``.  Used for
    env-state / obs / recurrent-carry auto-reset inside rollout scans —
    one definition so actor rollout and learner replay cannot diverge.
    """

    def expand(d, leaf):
        return d.reshape(d.shape + (1,) * (leaf.ndim - 1))

    return jax.tree.map(
        lambda fresh, cur: jnp.where(
            expand(done, cur), jnp.broadcast_to(fresh, cur.shape), cur
        ),
        fresh_tree,
        cur_tree,
    )


def profiler_workload(
    trainer: Any,
    state: Any,
    k: int,
    *,
    algo: str,
    params: Any,
    n_envs: int,
    horizon: int,
    update_epochs: int = 1,
    split_iters: int = 2,
) -> Dict[str, Any]:
    """Capture-time workload payload for a profiler bundle manifest
    (:meth:`~gymfx_tpu.telemetry.profiler.ProfilerSession.set_workload_source`):
    the dispatched program's optimized HLO (-> the rollout/update scope
    map), its XLA cost-model FLOPs, the analytic FLOP model, and the
    ``measure_phase_split`` baseline the report reconciles against.

    Runs OUTSIDE the capture window (after stop_trace) and pays one AOT
    recompile of the dispatched program plus the two phase sub-programs
    — only on capture supersteps.  ``measure_phase_split`` donates its
    input, so it runs on a copy of the live ``state``; never raises
    (the profiler counts a workload_error instead).
    """
    from gymfx_tpu.bench_util import compile_train_step, measure_phase_split

    info: Dict[str, Any] = {
        "algo": str(algo),
        "n_envs": int(n_envs),
        "horizon": int(horizon),
        "steps_per_iter": int(n_envs) * int(horizon),
    }
    k = max(1, int(k))
    compiled, flops = compile_train_step(trainer, state, None if k == 1 else k)
    info["hlo_text"] = compiled.as_text()
    info["xla_flops_per_dispatch"] = flops
    info["xla_flops_per_step"] = (flops / k) if flops else None
    try:
        from gymfx_tpu.telemetry.mfu import analytic_train_step_flops

        info["analytic_flops_per_step"] = analytic_train_step_flops(
            params, num_envs=int(n_envs), horizon=int(horizon),
            update_epochs=int(update_epochs),
        )
    except Exception:
        info["analytic_flops_per_step"] = None
    try:
        split = measure_phase_split(
            trainer, jax.tree.map(jnp.copy, state), int(split_iters)
        )
    except Exception:
        split = None
    if split is not None:
        rollout_s, update_s, _split_state, _u_flops = split
        info["phase_split"] = {
            "rollout_ms": rollout_s / int(split_iters) * 1e3,
            "update_ms": update_s / int(split_iters) * 1e3,
            "iters": int(split_iters),
            "source": "measure_phase_split",
        }
    else:
        info["phase_split"] = None
    return info
