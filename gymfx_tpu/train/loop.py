"""The one host training loop and the one from-config training flow of
the step-program trainers (PPO, IMPALA, portfolio PPO).

The jitted step programs are the trainers' own; everything the host does
round them is here, once: :func:`train_loop` places or initialises the
state, builds the metric stream, the mesh supervisor and the resilience
hooks (resilience/loop.py), registers what telemetry observes, and
dispatches supersteps; :func:`train_entry` is ``--mode training`` from a
config dict (envs, fault profile, mesh, trainer, resume, telemetry and
its ledger rows, the loop, held-out evaluation, the final checkpoint).

**The trainer's side of the contract** (what ``train_loop`` reads):

  ``ALGO``                   the name on the logger, the ``train/superstep``
                             span, the mesh-health and resilience gauges
  ``steps_per_iter``         env steps one train step collects
  ``nonfinite_guard``        whether the step carries the non-finite guard
                             (without it no skip watchdog runs)
  ``learner_params(state)``  the parameters a checkpoint's ``params`` item,
                             evaluation and the profiler's FLOP model take
  ``with_params(state, p)``  a params-only warm start (IMPALA sets the
                             learner's and the actors' copy)
  ``profiler_info()``        ``n_envs`` / ``horizon`` / ``update_epochs``
                             of the profiler bundle's workload payload
  ``init_state(seed)``, ``train_step(state)``, ``runtime``,
  ``STATE_PLAN``, ``curriculum`` (with one, ``_train_step_data`` and
  ``_train_many_data``), and ``train_many(state, k)`` where the trainer
  has supersteps; one without (the portfolio) runs at K = 1.

What differs between trainers in the from-config flow is a
:class:`TrainerSpec`, defined next to each trainer.

PBT is not here, by decision: ``PBTTrainer.train`` steps a vmapped
population and runs exploit/explore between steps; it shares the
summary's shape and no loop body, and ``_train_pbt_from_config`` has no
resume, no hooks and a params-only checkpoint.  Merging either would
make this module branch on its caller.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax

from gymfx_tpu.parallel.elastic import MeshSupervisor, elastic_entry
from gymfx_tpu.parallel.mesh import mesh_from_config, validate_batch_axis
from gymfx_tpu.resilience.faults import parse_fault_profile
from gymfx_tpu.resilience.loop import ResilientLoop
from gymfx_tpu.telemetry import (
    DelayedLogger,
    null_tracer,
    register_mesh_health,
    register_resilience,
    telemetry_from_config,
)
from gymfx_tpu.train.common import (
    labeled_eval_summary,
    profiler_workload,
    resolve_minibatch_scheme,
)


def train_loop(trainer, total_env_steps: int, *, seed: int = 0,
               log_every: int = 0, initial_state=None, initial_params=None,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 0, step_offset: int = 0,
               checkpoint_metadata: Optional[Dict[str, Any]] = None,
               max_consecutive_skips: int = 10,
               preempt_at: Optional[int] = None,
               supersteps_per_dispatch: int = 1,
               telemetry=None, mesh_faults=(), checkpoint_keep: int = 0):
    """Train for ~``total_env_steps``; returns ``(state, metrics)``.
    Logs every ``log_every`` iterations when > 0.  ``initial_state``
    continues a checkpointed run exactly (the full train state: params,
    optimizer state, env batch, RNG); ``initial_params`` is a
    params-only warm start.

    ``supersteps_per_dispatch=K > 1`` drives the loop through
    ``train_many``: one donated dispatch (and one host metrics fetch)
    per K iterations.  The iteration trajectory is bit-identical to
    K=1; checkpoints and preemption land on superstep boundaries.

    Resilience hooks (resilience/loop.py): ``checkpoint_every > 0``
    saves the full state every that many iterations (step ids are
    ``step_offset`` + env steps, so a resumed run keeps advancing;
    ``checkpoint_keep`` prunes to the newest N); under the non-finite
    guard, ``max_consecutive_skips`` fully-skipped steps in a row abort
    with NonFiniteDivergenceError; ``preempt_at`` raises a
    SimulatedPreemptionError after that iteration and ``mesh_faults``
    scripted device losses (checkpoint/resume and elastic drills).

    ``telemetry`` (a :class:`gymfx_tpu.telemetry.Telemetry` bundle,
    None = off) drains the superstep's on-device metric stack into its
    registry/sink once per dispatch and wraps each dispatch in a span:
    no extra host syncs either way, and with ``telemetry=None`` nothing
    is held or recorded."""
    restored = initial_state is not None or initial_params is not None
    state = trainer.init_state(seed) if initial_state is None else initial_state
    if initial_params is not None:
        state = trainer.with_params(state, initial_params)
    if restored and trainer.runtime is not None:
        # restored host arrays must enter the mesh placement (model-axis
        # tensor sharding) that init_state gives a fresh state
        state = trainer.runtime.place_state(state, trainer.STATE_PLAN)
    algo = trainer.ALGO
    steps_per_iter = trainer.steps_per_iter
    iters = max(1, int(total_env_steps) // steps_per_iter)
    K = 1
    if hasattr(trainer, "train_many"):
        K = max(1, int(supersteps_per_dispatch or 1))

    if telemetry is not None:
        logger = telemetry.device_stream(
            algo, iters=iters, log_every=log_every,
            steps_per_iter=steps_per_iter,
        )
    else:
        logger = DelayedLogger(algo, log_every, iters)
    # mesh health supervision (parallel/elastic.py): only when the run
    # has a mesh AND something observes it (scripted mesh faults or
    # telemetry), so the no-mesh/no-knobs path is untouched
    supervisor = None
    if trainer.runtime is not None and (mesh_faults or telemetry is not None):
        supervisor = MeshSupervisor(trainer.runtime.mesh)
    hooks = ResilientLoop(
        steps_per_iter=steps_per_iter,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        step_offset=step_offset,
        checkpoint_metadata=checkpoint_metadata,
        max_consecutive_skips=(
            max_consecutive_skips if trainer.nonfinite_guard else 0
        ),
        preempt_at=preempt_at,
        loggers=(logger,),
        ledger=telemetry.ledger if telemetry is not None else None,
        recorder=telemetry.recorder if telemetry is not None else None,
        profiler=telemetry.profiler if telemetry is not None else None,
        mesh_faults=tuple(mesh_faults or ()),
        supervisor=supervisor,
        checkpoint_keep=int(checkpoint_keep or 0),
    )
    if telemetry is not None:
        if supervisor is not None:
            register_mesh_health(telemetry.registry, supervisor, name=algo)
        if telemetry.profiler is not None:
            # late-binding over the rebound local: the manifest payload
            # (HLO scope map, FLOPs, phase split on a state copy) is
            # resolved at bundle-write time against the live state
            telemetry.profiler.set_workload_source(
                lambda it_start, kk: profiler_workload(
                    trainer, state, kk, algo=algo,
                    params=trainer.learner_params(state),
                    **trainer.profiler_info(),
                )
            )
        if telemetry.recorder is not None:
            # the closure reads the rebound local, so a postmortem dump
            # captures the rng key the run DIED with, not the seed key
            telemetry.recorder.set_rng_source(lambda: state.rng)
        if hooks.monitor is not None:
            register_resilience(
                telemetry.registry, monitor=hooks.monitor, name=algo
            )

    def checkpointed():
        return state._asdict(), trainer.learner_params(state)

    tracer = telemetry.tracer if telemetry is not None else null_tracer()
    t0 = time.perf_counter()
    metrics: Dict[str, Any] = {}
    it = 0
    while it < iters:
        k = min(K, iters - it)
        capturing = hooks.begin_superstep(it, k)
        # curriculum: one weighted seed-deterministic tape draw per
        # superstep boundary (ledgered as a curriculum_pick row)
        tape = None
        if trainer.curriculum is not None:
            _ti, _label, tape = trainer.curriculum.pick(it)
        with tracer.span("train/superstep", algo=algo, it=it, k=k):
            if k == 1:
                if tape is None:
                    state, metrics = trainer.train_step(state)
                else:
                    state, metrics = trainer._train_step_data(state, tape)
                guard_metrics = metrics
            else:
                if tape is None:
                    state, stacked = trainer.train_many(state, k)
                else:
                    state, stacked = trainer._train_many_data(state, tape, k)
                # newest iteration's metrics, still on device (no sync)
                metrics = jax.tree.map(lambda x: x[-1], stacked)
                guard_metrics = stacked
        if capturing:
            # the trace window must cover the device work, so the
            # async dispatch is synced, only on capture supersteps
            jax.block_until_ready(state)
        # logger BEFORE hooks: when the hooks abort (preemption,
        # divergence) they flush the attached logger, so the final
        # superstep's held metrics must already be in its hands
        logger.after_dispatch(it, k, guard_metrics)
        hooks.after_superstep(it, k, guard_metrics, checkpointed)
        it += k
    logger.finish()
    hooks.finish(checkpointed)
    jax.block_until_ready(trainer.learner_params(state))
    dt = time.perf_counter() - t0
    metrics = {key: float(value) for key, value in metrics.items()}
    metrics["env_steps_per_sec"] = steps_per_iter * iters / dt
    metrics["iterations"] = iters
    metrics["total_env_steps"] = steps_per_iter * iters
    if hooks.last_checkpoint_step is not None:
        metrics["last_checkpoint_step"] = hooks.last_checkpoint_step
    return state, metrics


class TrainerSpec(NamedTuple):
    """What differs between the trainers in :func:`train_entry`."""

    # config -> (train env, held-out env or None)
    build_envs: Callable[[Dict[str, Any]], Any]
    # config -> the trainer's own config tuple
    config_from: Callable[[Dict[str, Any]], Any]
    trainer_cls: Any
    state_cls: Any
    # (trainer config, env) -> the checkpoints' metadata
    checkpoint_metadata: Callable[[Any, Any], Dict[str, Any]]
    # (trainer, params, env or None = the trainer's own) -> the greedy
    # episode's summary
    evaluate: Callable[[Any, Any, Any], Dict[str, Any]]
    # (env.data, parsed fault profile) -> the contaminated feed; None
    # where the feed is not the MarketData the fault injectors take
    feed_faults: Optional[Callable[[Any, Dict[str, Any]], Any]] = None
    # env -> keys the summary holds beside the evaluation's
    summary_extra: Callable[[Any], Dict[str, Any]] = lambda env: {}


def train_entry(config: Dict[str, Any], spec: TrainerSpec) -> Dict[str, Any]:
    """CLI ``mode=training``: train, checkpoint where asked, and return
    the summary of a greedy evaluation with the training metrics in it.

    With ``elastic_resume`` set the run goes through the elastic
    auto-resume controller (parallel/elastic.py): a device loss re-plans
    the mesh over the survivors and resumes from the last
    digest-verified checkpoint; unset, this call IS the one run."""
    return elastic_entry(
        lambda cfg: _train_once(cfg, spec), config,
        # what an elastic run's survivor mesh must divide
        must_divide=(spec.config_from(config).n_envs,),
    )


def _train_once(config: Dict[str, Any], spec: TrainerSpec) -> Dict[str, Any]:
    from gymfx_tpu.train.checkpoint import resume_from_config, save_checkpoint

    env, eval_env = spec.build_envs(config)
    # chaos runs: the fault_profile knob contaminates the TRAINING feed
    # before the trainer closes over it (eval data stays clean so the
    # guard's effect is measurable)
    profile = parse_fault_profile(config.get("fault_profile"))
    if spec.feed_faults is not None and (
            profile["nan_bars"] or profile["inf_bars"]
            or profile.get("scengen")):
        env.data = spec.feed_faults(env.data, profile)
    tcfg = spec.config_from(config)
    if hasattr(tcfg, "minibatch_scheme"):
        # (IMPALA takes one update a step and has none.)  The resolution
        # may rewrite the config's scheme, so the tuple is read again
        resolve_minibatch_scheme(config, tcfg.n_envs, tcfg.minibatches)
        tcfg = spec.config_from(config)
    mesh = mesh_from_config(config)
    validate_batch_axis(mesh, tcfg.n_envs, "num_envs")
    trainer = spec.trainer_cls(env, tcfg, mesh=mesh)
    # full-state checkpoints continue the exact trajectory (opt moments,
    # env batch, RNG); params-only ones warm-start
    resume_state, resume_params, resume_step = resume_from_config(
        config, trainer, spec.state_cls
    )
    ckpt_meta = spec.checkpoint_metadata(tcfg, env)
    keep = int(config.get("checkpoint_keep", 0) or 0)
    telemetry = telemetry_from_config(config)
    if telemetry is not None and telemetry.ledger is not None and (
            resume_state is not None or resume_params is not None):
        telemetry.ledger.record("checkpoint_restore", step=int(resume_step))
        if config.get("elastic_attempt"):
            # elastic re-entry: the restore above came back through the
            # digest-verified path and re-enters the SURVIVOR mesh plan
            telemetry.ledger.record(
                "mesh_resume", step=int(resume_step),
                attempt=int(config["elastic_attempt"]), verified=True,
                mesh_shape=dict(mesh.shape) if mesh is not None else None,
            )
    try:
        state, train_metrics = train_loop(
            trainer, int(config.get("train_total_steps", 1_000_000)),
            seed=int(config.get("seed", 0) or 0),
            initial_params=resume_params, initial_state=resume_state,
            checkpoint_dir=config.get("checkpoint_dir"),
            checkpoint_every=int(config.get("checkpoint_every", 0) or 0),
            step_offset=resume_step,
            checkpoint_metadata=ckpt_meta,
            max_consecutive_skips=int(
                config.get("guard_max_consecutive_skips", 10) or 0
            ),
            preempt_at=profile.get("preempt_at"),
            supersteps_per_dispatch=int(
                config.get("supersteps_per_dispatch", 1) or 1
            ),
            telemetry=telemetry,
            mesh_faults=profile.get("mesh") or (),
            checkpoint_keep=keep,
        )
    except BaseException:
        # abort paths (preemption drill, divergence) still seal the run
        # ledger with its run_end row; the postmortem bundle was
        # already dumped by ResilientLoop before the raise
        if telemetry is not None:
            telemetry.close()
        raise
    if telemetry is not None:
        if telemetry.sink is not None:
            telemetry.sink.append({
                "kind": "metrics_snapshot", "algo": trainer.ALGO,
                "registry": telemetry.registry.snapshot(),
            })
        telemetry.close()

    # out-of-sample: greedy episode on bars the agent never trained on;
    # the in-sample numbers ride along for the generalization gap
    params = trainer.learner_params(state)
    summary = labeled_eval_summary(
        lambda e: spec.evaluate(trainer, params, e), env, eval_env
    )
    summary.update(spec.summary_extra(env))
    summary["train_metrics"] = train_metrics
    if mesh is not None:
        summary["mesh_shape"] = dict(mesh.shape)

    ckpt_dir = config.get("checkpoint_dir")
    if ckpt_dir:
        # cumulative step count: orbax silently skips saving a step that
        # already exists, so a resumed run must advance past the loaded
        # step; a periodic auto-checkpoint that already landed on the
        # final step makes this save redundant
        final_step = resume_step + train_metrics["total_env_steps"]
        if train_metrics.get("last_checkpoint_step") != final_step:
            save_checkpoint(
                ckpt_dir, state._asdict(), step=final_step,
                metadata=ckpt_meta, params=params, keep=keep,
                protect=(int(resume_step),),
            )
        summary["checkpoint_dir"] = str(ckpt_dir)
    return summary
