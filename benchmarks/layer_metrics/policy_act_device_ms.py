"""Device self time per train step of the fused step (scope_times.py),
``rollout/policy_act``: the policy forward of the rollout
(attention and feed-forward blocks included), sampling, ``log_softmax`` and
the pick of the action's log-probability."""
from scope_times import ms


def read(run):
    return ms(run, "rollout/policy_act")
