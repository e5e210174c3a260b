"""``correct`` for a trainer cell whose kernels have a plain-XLA twin in the
program: the first steps of the measured program against the same steps of
the plain path (the configuration with ``spec["overrides"]``) at the SAME
seed and the SAME shapes.  A copy of ``chip_smoke.compare_runs`` and
``tree_diff``: the first step's loss must agree within ``loss_rtol``; how
far metrics, env state and params agree bitwise is reported beside it
(PR 22 found the env-dynamics kernels bitwise on the chip)."""
from __future__ import annotations

import gc


def tree_diff(a, b):
    """(bitwise_equal, max_abs_diff over differing leaves, n_leaves_differing)"""
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False, float("inf"), max(len(la), len(lb))
    worst, differing = 0.0, 0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False, float("inf"), len(la)
        if not np.array_equal(x, y, equal_nan=x.dtype.kind == "f"):
            differing += 1
            d = np.abs(x.astype(np.float64) - y.astype(np.float64))
            worst = max(worst, float(np.nanmax(d)))
    return differing == 0, worst, differing


def reference(ctx, spec, build, first_steps):
    """The plain path's first steps, copied to the host; its trainer and
    state are freed before the measured program is built."""
    state, step, facts = first_steps(build(**spec["overrides"]))
    del state, step
    gc.collect()
    return facts


def verdict(spec, ref, got):
    loss_ref = [float(m["loss"]) for m in ref["metrics"]]
    loss_got = [float(m["loss"]) for m in got["metrics"]]
    first = abs(loss_got[0] - loss_ref[0])
    bit_metrics, worst_metric, _ = tree_diff(got["metrics"], ref["metrics"])
    bit_env, worst_env, env_leaves = tree_diff(got["env_states"], ref["env_states"])
    bit_params, worst_params, _ = tree_diff(got["params"], ref["params"])
    ok = first <= float(spec["loss_rtol"]) * max(1.0, abs(loss_ref[0]))
    return ok, {
        "kind": "plain_twin", "loss": loss_got, "plain_loss": loss_ref,
        "first_step_loss_abs_diff": first,
        "plain_tpu_custom_calls": ref["tpu_custom_calls"],
        "metrics_bitwise": bit_metrics, "metrics_max_abs_diff": worst_metric,
        "env_states_bitwise": bit_env, "env_states_max_abs_diff": worst_env,
        "env_state_leaves_differing": env_leaves,
        "params_bitwise": bit_params, "params_max_abs_diff": worst_params,
    }
