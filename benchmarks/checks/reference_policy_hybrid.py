"""``correct`` for a trainer cell whose policy is the HYBRID decoder trunk
(linear-attention layers beside latent attention): ``checks/reference_policy.py``
as it stands -- the twin's bit-for-bit repeat, the recorded log-probabilities
and values, the routing flips, the step's loss, the parameters' change over all
minibatches -- with the parameter groups of the reference-shaped tree of
``checks/hybrid_decoder_reference.py`` among the candidates for the worst group:
the linear-attention layer's projections, convolutions, decay gate, beta, and
output path, and the latent-attention layer's full-rank query and head gate.
That file's ``GROUPS`` is a module global it reads when it judges an update; this
module loads a copy of its own and lays the further groups on it, and one further
distance on its ``update_distance`` (``update_norm_shortfall``, below)."""
import harness

_base = harness.load_module("checks", "reference_policy")
_base.GROUPS = {
    **_base.GROUPS,
    "mla_q": ("q",), "head_gate": ("head_gate",),
    "kda_qkv": ("kda_q", "kda_k", "kda_v"),
    "kda_conv": ("kda_q_conv", "kda_k_conv", "kda_v_conv"),
    "kda_decay": ("kda_f", "kda_dt_bias", "kda_A_log"),
    "kda_beta": ("kda_b",),
    "kda_out": ("kda_g", "kda_o", "kda_o_norm"),
}
_distance = _base.update_distance


def update_distance(after, ref_after, before):
    """That file's distances, and ``update_norm_shortfall``: ``|1 - |d| / |d_ref||``,
    how far the SIZE of the step's change is from the reference's.  Adam's first
    updates are near ``-lr sign(g)``: a gradient a fifth wrong turns enough signs
    to read 0.5 in ``update_rel_l2`` and leaves the size where it was, while a
    minibatch left out, another learning rate or a state left unchanged move the
    size and nothing else needs to."""
    import jax.numpy as jnp

    def norm(tree):
        return sum(float(jnp.sum(jnp.square(jnp.asarray(a) - jnp.asarray(b))))
                   for leaves, base in zip(_base.by_group(tree).values(),
                                           _base.by_group(before).values())
                   for a, b in zip(leaves, base)) ** 0.5

    out = _distance(after, ref_after, before)
    out["update_norm_shortfall"] = abs(1.0 - norm(after) / max(norm(ref_after), 1e-30))
    return out


_base.update_distance = update_distance
reference, verdict = _base.reference, _base.verdict
