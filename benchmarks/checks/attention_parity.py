"""``correct`` for a trainer cell whose only kernel is fused window
attention: the program has no option that takes the kernel out on a TPU
(``policies.dense_window_attention`` picks it by window and device), and a
whole plain-attention step would not fit beside the measured one.  So the
kernel is held against the plain reference at the cell's OWN attention
shape and dtype: ``fused_window_attention`` vs ``full_attention``, output
and the q/k/v gradients of ``sum(out ** 2)`` — a copy of
``chip_smoke._attention_parity``.  The reference runs under
``jax.default_matmul_precision("highest")`` on the same (rounded) inputs.
Tolerances are in the configuration file, with their reason."""
from __future__ import annotations


def reference(ctx, spec, build, first_steps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[spec["dtype"]]
    shape = tuple(spec["shape"])
    keys = jax.random.split(jax.random.PRNGKey(harness.seed31(ctx.seed, 1)), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
               for key in keys)
    interpret = bool(ctx.rehearse)

    def fused_fn(q, k, v):
        return fused_window_attention(q, k, v, interpret=interpret)

    def grad_of(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))

    fused = jax.jit(fused_fn).lower(q, k, v).compile()
    fused_grad = grad_of(fused_fn).lower(q, k, v).compile()
    calls = min(fused.as_text().count("tpu_custom_call"),
                fused_grad.as_text().count("tpu_custom_call"))
    got, got_grad = fused(q, k, v), fused_grad(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(full_attention)(q, k, v)
        ref_grad = grad_of(full_attention)(q, k, v)

    def worst(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))

    def scale(a):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32))))

    return {
        "kind": "attention_parity", "shape": list(shape), "dtype": spec["dtype"],
        "kernel_tpu_custom_calls": calls,
        "finite": bool(np.isfinite(np.asarray(got, np.float32)).all()),
        "out_max_abs_diff": worst(got, ref), "out_max_abs": scale(ref),
        "grad_max_abs_diff": max(worst(a, b) for a, b in zip(got_grad, ref_grad)),
        "grad_max_abs": max(scale(b) for b in ref_grad),
    }


def verdict(spec, ref, got):
    ok = (ref["finite"]
          and ref["kernel_tpu_custom_calls"] >= int(spec["kernel_calls_expected"])
          and ref["out_max_abs_diff"] <= float(spec["out_atol"])
          and ref["grad_max_abs_diff"] <= float(spec["grad_atol"]))
    return ok, ref
