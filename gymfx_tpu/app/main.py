#!/usr/bin/env python3
"""CLI runner — the reference's env-only runtime surface
(reference app/main.py:34-96): parse args, merge the layered config,
instantiate the six plugin families, run the driver loop, write the
results JSON, optionally save the non-default config, print the summary.

New capability beyond the reference (which validates the mode but runs
the same episode loop for all three): ``mode=training`` routes to the
PPO / IMPALA / PBT / portfolio trainers, ``mode=optimization`` runs the
vmapped hyperparameter search, and ``driver_mode=policy`` evaluates a
checkpointed policy.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from gymfx_tpu.config import DEFAULT_VALUES, load_config, merge_config, save_config
from gymfx_tpu.config.cli import parse_args
from gymfx_tpu.config.merger import process_unknown_args
from gymfx_tpu.gym_env import build_environment
from gymfx_tpu.plugins import get_plugin_params


PLUGIN_GROUPS = {
    "data_feed_plugin": "data_feed.plugins",
    "broker_plugin": "broker.plugins",
    "strategy_plugin": "strategy.plugins",
    "preprocessor_plugin": "preprocessor.plugins",
    "reward_plugin": "reward.plugins",
    "metrics_plugin": "metrics.plugins",
}


def _collect_plugin_defaults(config: Dict[str, Any]) -> Dict[str, Any]:
    merged: Dict[str, Any] = {}
    for key, group in PLUGIN_GROUPS.items():
        name = str(config[key])
        try:
            merged.update(get_plugin_params(group, name))
        except ImportError:
            # registered compute KERNELS (plugins/kernels.py) are selected
            # through the same strategy_plugin/reward_plugin keys; their
            # declared parameter defaults join the merge identically
            from gymfx_tpu.plugins import kernels as _k

            kernel_group = {
                "strategy_plugin": _k.STRATEGY_GROUP,
                "reward_plugin": _k.REWARD_GROUP,
            }.get(key)
            if kernel_group is None or not _has_kernel(kernel_group, name):
                raise
            merged.update(get_plugin_params(kernel_group, name))
    return merged


def _has_kernel(group: str, name: str) -> bool:
    from gymfx_tpu.plugins.registry import available

    return name in available(group)


def make_cli_driver(config: Dict[str, Any]):
    """Host-side diagnostic action source
    (reference strategy_plugins/default_strategy.py:44-54)."""
    mode = str(config.get("driver_mode", "buy_hold"))
    seed = config.get("seed")
    rng = np.random.default_rng(seed)
    if mode == "replay":
        path = config.get("replay_actions_file")
        if not path:
            raise ValueError("driver_mode=replay requires replay_actions_file")
        import csv

        with open(path, "r", encoding="utf-8") as fh:
            actions: List[int] = [int(row.get("action", 0)) for row in csv.DictReader(fh)]

        def replay(obs, info, step):
            return actions[step] if step < len(actions) else 0

        return replay
    if mode == "random":
        return lambda obs, info, step: int(rng.integers(0, 3))
    if mode == "flat":
        return lambda obs, info, step: 0
    if mode == "buy_hold":
        return lambda obs, info, step: 1 if step == 0 else 0
    raise ValueError(f"unknown driver_mode {mode!r}")


def run_mode(config: Dict[str, Any]) -> Dict[str, Any]:
    """Dispatch: ``mode=training`` runs the PPO trainer;
    ``driver_mode=policy`` restores a checkpoint and runs a greedy
    evaluation episode; everything else runs the diagnostic episode
    loop (the reference validates the mode but runs the same loop for
    all three — app/main.py:84; training/policy are new capability)."""
    if config.get("mode") == "training":
        from gymfx_tpu.train import impala, pbt, portfolio_ppo, ppo
        from gymfx_tpu.train.loop import train_entry

        trainer = str(config.get("trainer", "ppo")).lower()
        if trainer == "pbt":
            return pbt.train_pbt_from_config(config)
        specs = {"impala": impala.SPEC, "portfolio": portfolio_ppo.SPEC}
        return train_entry(config, specs.get(trainer, ppo.SPEC))
    if config.get("mode") == "optimization":
        from gymfx_tpu.train.optimize import optimize_from_config

        return optimize_from_config(config)
    if config.get("driver_mode") == "policy":
        if config.get("export_scaled_features"):
            raise ValueError(
                "export_scaled_features is supported on the scanned "
                "diagnostic episode path only; run the export as a "
                "separate inference invocation"
            )
        if config.get("portfolio_files"):
            from gymfx_tpu.train.portfolio_ppo import (
                eval_portfolio_policy_from_config,
            )

            return eval_portfolio_policy_from_config(config)
        from gymfx_tpu.train.ppo import eval_policy_from_config

        return eval_policy_from_config(config)
    return _run_env(config)


def _run_env(config: Dict[str, Any]) -> Dict[str, Any]:
    # plugin defaults re-merge (lowest precedence — reference main.py:44-46)
    config = merge_config(config, _collect_plugin_defaults(config), {}, {}, {}, {})

    # Built-in drivers run as ONE scanned XLA episode instead of a
    # per-step python loop (each per-step dispatch is a host->device
    # round trip; a scan pays it once per episode).  Identical
    # broker/reward/diagnostics semantics; set gym_loop=true to force
    # the step-by-step Gymnasium path (e.g. for custom host drivers).
    mode = str(config.get("driver_mode", "buy_hold"))
    if mode in ("buy_hold", "flat", "random", "replay") and not config.get("gym_loop"):
        return _run_env_scan(config)

    if config.get("export_scaled_features"):
        # honor-or-reject: the export is a scan-path feature (it reads
        # the Environment's precomputed feature tensors) — silently
        # producing no file would strand a downstream pipeline
        raise ValueError(
            "export_scaled_features is supported on the scanned episode "
            "path only (builtin driver_mode without gym_loop); run the "
            "export as a separate inference invocation"
        )

    env = build_environment(config=config)
    decide = make_cli_driver(config)
    try:
        obs, info = env.reset(seed=config.get("seed"))
        done = False
        steps = int(config.get("steps", 500))
        step_count = 0
        while not done and step_count < steps:
            action = decide(obs, info, step_count)
            obs, _, terminated, truncated, info = env.step(action)
            done = bool(terminated or truncated)
            step_count += 1
        return env.summary()
    finally:
        env.close()


def _export_scaled_features(env, config, n_steps: int, path: str):
    """Materialize the episode's scaled feature windows
    ``(n_steps, window, F)`` and save them (.npz) for external ML
    pipelines — the reference preprocessor family's raison d'etre
    (reference preprocessor_plugins/feature_window_preprocessor.py
    produces exactly these windows for a consumer model).

    This is the product caller of the fused pallas kernel
    (ops/window_zscore.py batched_scaled_windows): the IN-SCAN path
    keeps the O(1)-per-step streaming carry (cheaper than any batched
    materialization inside the episode), while this BATCHED
    materialization — many steps at once — is the kernel's shape, ~1.6x
    the jitted-XLA twin on chip (examples/results/
    pallas_kernel_bench.json)."""
    import jax

    from gymfx_tpu.ops.window_zscore import batched_scaled_windows

    cfg = env.cfg
    data = (
        env.require_resident_data("export_scaled_features")
        if hasattr(env, "require_resident_data") else env.data
    )
    if cfg.n_features == 0:
        raise ValueError(
            "export_scaled_features requires feature_columns in the config "
            "(the scaled windows ARE the feature-window preprocessor's "
            "output)"
        )
    import jax.numpy as jnp

    w = cfg.window_size
    steps = jnp.arange(1, n_steps + 1, dtype=jnp.int32)
    windows = batched_scaled_windows(
        data.padded_features, data.feat_mean, data.feat_std,
        data.feat_neutral, steps,
        window=w, clip=float(cfg.feature_clip or 0.0),
    )
    arr = np.array(jax.device_get(windows), np.float32)
    if any(cfg.binary_mask):
        # binary passthrough columns carry raw values, exactly like the
        # obs path (core/obs.py build_obs)
        from numpy.lib.stride_tricks import sliding_window_view

        raw = np.asarray(jax.device_get(data.padded_features), np.float32)
        steps_np = np.arange(1, n_steps + 1)
        clip = float(cfg.feature_clip or 0.0)
        for j, is_bin in enumerate(cfg.binary_mask):
            if is_bin:
                col = sliding_window_view(raw[:, j], w)[steps_np]
                # match build_obs (core/obs.py): passthrough values still
                # go through the clip + nan_to_num clamp
                if clip > 0:
                    col = np.clip(col, -clip, clip)
                arr[:, :, j] = np.nan_to_num(
                    col, nan=0.0, posinf=clip, neginf=-clip
                )
    columns = [str(c) for c in (env.config.get("feature_columns") or [])]
    np.savez_compressed(
        path, scaled_windows=arr, feature_columns=np.asarray(columns)
    )
    return {"path": path, "shape": list(arr.shape), "columns": columns}


def _run_env_scan(config: Dict[str, Any]) -> Dict[str, Any]:
    """One lax.scan episode + host-side summary (same shape as the
    Gymnasium-loop path; reference summary surface app/env.py:697-716)."""
    import jax

    from gymfx_tpu.core.runtime import Environment
    from gymfx_tpu.core.types import ACTION_DIAG_KEYS, EXEC_DIAG_KEYS
    from gymfx_tpu.metrics import compute_analyzers, summarize_default, summarize_trading

    env = Environment(config)
    driver = env.make_driver()
    steps = int(config.get("steps", 500))
    seed = int(config.get("seed", 0) or 0)
    n_envs = int(config.get("num_envs", 1) or 1)
    batch_stats = None
    if n_envs > 1:
        if env.streaming:
            env.require_resident_data("num_envs > 1 batch evaluation")
        # batch evaluation (new capability): vmap the whole episode over
        # per-env rng streams and aggregate outcome statistics; the
        # detailed summary below reports env 0's episode
        # vmap over the CHUNKED host loop so compile cost stays
        # independent of episode length (see rollout_chunked)
        import jax.numpy as jnp

        from gymfx_tpu.core import env as env_core
        from gymfx_tpu.core.rollout import _rollout_chunk

        keys = jax.random.split(jax.random.PRNGKey(seed), n_envs)
        vreset = jax.jit(jax.vmap(
            lambda _i: env_core.reset(env.cfg, env.params, env.data),
            in_axes=0,
        ))
        states_b, obs_b = vreset(jnp.arange(n_envs))

        def chunk_call(chunk_len, states_b, obs_b, keys_b, offset):
            f = jax.vmap(
                lambda st, ob, k: _rollout_chunk(
                    env.cfg, env.params, env.data, driver, chunk_len,
                    st, ob, k, (), jnp.asarray(offset, jnp.int32), True,
                )
            )
            return f(states_b, obs_b, keys_b)

        pieces = []
        done_steps = 0
        while done_steps < steps:
            this = min(64, steps - done_steps)
            states_b, obs_b, keys, _dc, out_piece = chunk_call(
                this, states_b, obs_b, keys, done_steps
            )
            pieces.append(out_piece)
            done_steps += this
        out_b = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *pieces)
        states_b, out_b = jax.device_get((states_b, out_b))
        finals = np.asarray(out_b["equity_delta"], np.float64)[:, -1]
        returns = finals / float(config.get("initial_cash", 10000.0))
        batch_stats = {
            "num_envs": n_envs,
            "mean_total_return": float(returns.mean()),
            "std_total_return": float(returns.std(ddof=1)),
            "min_total_return": float(returns.min()),
            "max_total_return": float(returns.max()),
            "mean_trades": float(np.asarray(states_b.trade_count).mean()),
        }
        state = jax.tree.map(lambda x: x[0], states_b)
        out = jax.tree.map(lambda x: x[0], out_b)
    else:
        state, out = env.rollout(driver, steps, seed=seed)
        state, out = jax.device_get((state, out))

    equity = np.asarray(out["equity_delta"], np.float64) + float(
        config.get("initial_cash", 10000.0)
    )
    done = np.asarray(out["done"], bool)
    n_steps = int(np.argmax(done)) + 1 if done.any() else steps
    ts = env.dataset.timestamps.iloc[1 : n_steps + 1]
    analyzers = compute_analyzers(
        equity=equity, done=done, state=state, timestamps=ts
    )
    final_equity = float(equity[n_steps - 1])
    name = str(config.get("metrics_plugin", "default_metrics"))
    summarize = {"default_metrics": summarize_default,
                 "trading_metrics": summarize_trading}.get(name)
    if summarize is None:  # third-party plugin from the registry
        from gymfx_tpu.plugins import get_plugin

        summarize = get_plugin("metrics.plugins", name)(config)
    summary = summarize(
        initial_cash=float(config.get("initial_cash", 10000.0)),
        final_equity=final_equity,
        analyzers=analyzers,
        config=config,
    )
    action_diag = {
        key: int(state.action_diag[i]) for i, key in enumerate(ACTION_DIAG_KEYS)
    }
    action_diag["raw_abs_sum"] = float(state.raw_abs_sum)
    has_steps = action_diag["steps"] > 0
    action_diag["raw_min"] = float(state.raw_min) if has_steps else None
    action_diag["raw_max"] = float(state.raw_max) if has_steps else None
    action_diag["continuous_action_threshold"] = (
        float(config.get("continuous_action_threshold", 0.33) or 0.33)
        if str(config.get("action_space_mode", "discrete")) == "continuous"
        else None
    )
    summary["action_diagnostics"] = action_diag
    summary["execution_diagnostics"] = {
        key: int(state.exec_diag[i]) for i, key in enumerate(EXEC_DIAG_KEYS)
    }
    record_path = config.get("record_actions_file")
    if record_path:
        # persist the executed action stream in the replay schema
        # (driver_mode=replay consumes it — reference
        # strategy_plugins/default_strategy.py:38-42)
        import csv

        with open(record_path, "w", encoding="utf-8", newline="") as fh:
            writer = csv.writer(fh)
            writer.writerow(["action"])
            for a in np.asarray(out["action"])[:n_steps]:
                writer.writerow([int(a)])
        summary["record_actions_file"] = str(record_path)

    export_path = config.get("export_scaled_features")
    if export_path:
        summary["export_scaled_features"] = _export_scaled_features(
            env, config, n_steps, str(export_path)
        )

    if "event_context" in out:
        # event fields of the last executed (pre-termination) step,
        # matching the Gymnasium-loop path's last-info snapshot
        last = n_steps - 1
        summary["event_context_diagnostics"] = {
            k: np.asarray(v)[last].item()
            for k, v in out["event_context"].items()
        }
    else:
        summary["event_context_diagnostics"] = {}
    if batch_stats is not None:
        summary["batch"] = batch_stats
    if config.get("verify_execution"):
        # independent-engine verification (the reference's Nautilus-env
        # role): replay env 0's executed action stream through the
        # float64 replay engine and reconcile the realized balances.
        # The scan side is NOT re-run — this episode's final state is
        # reused.  Unsupported configs record a skip, never abort a
        # finished run.
        from gymfx_tpu.simulation.crosscheck import crosscheck_episode

        # done fires on dataset exhaustion as well as bankruptcy
        # (core/env.py termination); only bankruptcy invalidates the
        # cross-check — an exhausted episode is a complete action
        # stream.  The env records the reason explicitly (a bankruptcy
        # ON the final bar would fool any bar-cursor heuristic).
        from gymfx_tpu.core.types import TERMINATION_BANKRUPT

        bankrupt = (
            int(np.asarray(jax.device_get(state.termination_reason)))
            == TERMINATION_BANKRUPT
        )
        try:
            summary["execution_crosscheck"] = crosscheck_episode(
                config,
                seed=seed,
                env=env,
                scan_state=state,
                trace=out,
                terminated=bankrupt,
            )
        except (ValueError, TypeError) as exc:
            # TypeError covers null-valued instrument keys in a config
            # file (int(None) in the spec resolver) — a skipped
            # verification must never abort a finished run
            summary["execution_crosscheck"] = {
                "status": "skipped",
                "reason": f"{type(exc).__name__}: {exc}",
            }
    return summary


def main(argv=None) -> Dict[str, Any]:
    args, unknown = parse_args(argv)
    cli_args = vars(args)

    config = DEFAULT_VALUES.copy()
    file_config = load_config(args.load_config) if args.load_config else {}
    unknown_dict = process_unknown_args(unknown)
    config = merge_config(config, {}, {}, file_config, cli_args, unknown_dict)

    if config.get("mode") not in {"training", "optimization", "inference"}:
        raise ValueError("mode must be one of training|optimization|inference")

    from gymfx_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    summary = run_mode(config)

    results_file = Path(config.get("results_file") or "results.json")
    results_file.parent.mkdir(parents=True, exist_ok=True)
    with results_file.open("w", encoding="utf-8") as fh:
        json.dump(summary, fh, indent=2, default=str)

    if config.get("save_config"):
        save_config(config, config["save_config"])

    if not config.get("quiet_mode", False):
        print(json.dumps(summary, indent=2, default=str))
    return summary


if __name__ == "__main__":
    main()
