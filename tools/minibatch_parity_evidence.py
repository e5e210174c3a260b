#!/usr/bin/env python3
"""Held-out learning parity: env_permute vs sample_permute minibatches
-> examples/results/minibatch_scheme_parity.json.

Round 6 makes ``ppo_minibatch_scheme=env_permute`` the product default
(config/defaults.py): trajectory (env-permuted) minibatches turn the
update phase's T*N random sample gather into contiguous whole-
trajectory DMA, which is what closed the wide-batch rollover on TPU
(tools/tpu_bench.py sweep; its committed artifact was deleted in PR 22
and is not measured on today's code).  A default flip needs quality
evidence, not just speed evidence — this tool trains the flagship
recipe under BOTH schemes across several seeds with only the minibatch
scheme differing, evaluates every run on the chronological holdout,
and commits the whole grid so the claim is reproducible.

The two schemes see the same trajectories but different minibatch
compositions, so the comparison is statistical, not bitwise, and
single-seed Sharpe at CPU-feasible scale is NOISY (a one-seed pilot of
this tool saw sample_permute land at -67 where env_permute held +59 on
the identical config) — hence seeds x schemes and a median-based gate.
The gate is the one a default flip actually needs: env_permute must
show NO held-out regression vs sample_permute (median Sharpe at least
as good, or within the half-band noise floor).  The artifact records
the device it ran on; the committed copy is a CPU run at CPU-feasible
scale (the scheme choice is dtype- and backend-invariant — identical
program semantics, only the gather pattern differs).

Usage: python tools/minibatch_parity_evidence.py [--quick] [--output PATH]
"""
from __future__ import annotations

import argparse
import datetime
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from gymfx_tpu.compile_cache import enable_compile_cache

enable_compile_cache()

SCHEMES = ("env_permute", "sample_permute")


def run_scheme(base_config: dict, scheme: str, seed: int) -> dict:
    from gymfx_tpu.train.ppo import train_from_config

    t0 = time.perf_counter()
    summary = train_from_config(
        dict(base_config, ppo_minibatch_scheme=scheme, seed=seed)
    )
    assert summary["eval_scope"] == "held_out", summary.get("eval_scope")
    return {
        "scheme": scheme,
        "seed": seed,
        "sharpe_held_out": summary["sharpe_ratio_steps"],
        "total_return_held_out": summary["total_return"],
        "trades_held_out": summary["trades_total"],
        "max_drawdown_pct_held_out": summary["max_drawdown_pct"],
        "sharpe_in_sample": summary["in_sample"]["sharpe_ratio_steps"],
        "env_steps": summary["train_metrics"]["total_env_steps"],
        "wall_clock_seconds": round(time.perf_counter() - t0, 2),
    }


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny run (CI smoke; artifact not written)")
    ap.add_argument(
        "--output", default="examples/results/minibatch_scheme_parity.json"
    )
    ap.add_argument("--train_total_steps", type=int, default=1_048_576)
    ap.add_argument("--seeds", type=int, nargs="+", default=[7, 11, 23])
    args = ap.parse_args()

    import jax

    from make_example_data import ensure_m1_quarter

    from gymfx_tpu.config import DEFAULT_VALUES

    # the train_to_sharpe recipe (BASELINE config 3 + feature windows)
    # at CPU-feasible scale: same learnable synthetic series, same
    # chronological 25% holdout, smaller env batch / step budget
    config = dict(DEFAULT_VALUES)
    config.update(
        input_data_file=str(
            ensure_m1_quarter(path="/tmp/m1_parity.csv", n=20_000)
        ),
        eval_split=0.25,
        num_envs=128, ppo_horizon=32, ppo_epochs=2, ppo_minibatches=4,
        position_size=1000.0, random_episode_start=True,
        policy="mlp", policy_dtype="bfloat16",
        reward_plugin="sharpe_reward", strategy_plugin="direct_atr_sltp",
        feature_columns=["CLOSE", "RET1", "RET5"],
        feature_scaling="rolling_zscore", feature_scaling_window=64,
        gamma=0.9, learning_rate=2e-4,
        train_total_steps=args.train_total_steps,
    )
    if args.quick:
        config.update(
            input_data_file=str(
                ensure_m1_quarter(path="/tmp/m1_quick.csv", n=4000)
            ),
            num_envs=32, ppo_horizon=8, train_total_steps=512,
        )
        args.seeds = args.seeds[:1]

    runs = [
        run_scheme(config, s, seed)
        for seed in args.seeds
        for s in SCHEMES
    ]
    for r in runs:
        print(json.dumps(r), flush=True)
    sh = {
        s: [r["sharpe_held_out"] for r in runs if r["scheme"] == s]
        for s in SCHEMES
    }
    both = all(v is not None for vs in sh.values() for v in vs)
    med = {s: (_median(sh[s]) if both else None) for s in SCHEMES}
    # the gate a default flip needs: the new default's median held-out
    # Sharpe is no worse than the old scheme's, up to a half-band noise
    # floor (seed-to-seed spread at this scale dwarfs any scheme effect)
    no_regression = bool(
        both
        and med["env_permute"] >= med["sample_permute"]
        - 0.5 * max(abs(med["sample_permute"]), 1.0)
    )
    device = jax.devices()[0]
    artifact = {
        "schema": "minibatch_scheme_parity.v1",
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "device": str(getattr(device, "device_kind", device.platform)),
        "platform": device.platform,
        "claim": "ppo_minibatch_scheme=env_permute (the r6 product "
                 "default) shows no held-out learning regression vs "
                 "sample_permute on the train-to-sharpe recipe across "
                 "seeds; the schemes differ only in minibatch "
                 "composition, so the comparison is statistical (median "
                 "over seeds), not bitwise",
        "no_regression": no_regression,
        "median_sharpe_held_out": med,
        "seeds": args.seeds,
        "config": {
            k: config[k]
            for k in (
                "num_envs", "ppo_horizon", "ppo_epochs", "ppo_minibatches",
                "train_total_steps", "eval_split",
                "reward_plugin", "strategy_plugin", "learning_rate",
            )
        },
        "runs": runs,
    }
    print(json.dumps(
        {"no_regression": no_regression, "median_sharpe_held_out": med}
    ), flush=True)
    if args.quick:
        return 0
    if not no_regression:
        print("REFUSING to write artifact: env_permute REGRESSES "
              f"held-out quality ({med})", file=sys.stderr)
        return 1
    out = Path(args.output)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(artifact, indent=1))
    print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
