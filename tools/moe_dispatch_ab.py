#!/usr/bin/env python3
"""Chip A/B of the expert layer's way back from the sorted buffer
(``train/mla_moe_decoder.py``: ``routing_plan``, ``dispatch_rows``,
``combine_rows``), one JSON line a shape and variant.  At the three decoder
cells' shapes (tokens, hidden, k, held of n; ``SHAPES``), through the short
buffer and through the worst case's, milliseconds on the chip of

  ``plan_ms``              the plan alone
  ``sum_ms``               the weighted sum back alone, bfloat16
  ``forward_ms``           plan, rows to the buffer, weighted sum back
  ``forward_backward_ms``  the same with the gradients of tokens and weights

for the way back by k gathers of T rows (``k_gathers``: the module's) and
through the buffer's rows in TOKEN order (``token_order``: what ISSUE 36
proposed, restated here and in no program: the plan also places every held
choice token-major, ONE gather of the buffer's R rows into that order, a
token's run summed at its head by k - 1 shifted adds, ONE gather of T heads;
it lost at every shape, PERF.md section 6), and of the weight gradient's three
forms (``WEIGHT_GRADIENTS``: k gathers of T rows of ``ys`` as before PR 36; the
rows' own float32 dots placed by ONE scatter of R scalars, shipped; the same
dots fetched by one gather of k * T scalars).  For the record: the rows to the
buffer with the padding rows zeroed (before PR 36) and as they come
(``rows_there_ms``), and one gather of T rows of ``bfloat16[R, hidden]`` beside
the same bytes as ``uint32[R, hidden / 2]`` (``rows_ms``), which says whether
the cost a row is the 16-bit tiling's.  A call timed alone is NOT the call
inside the step: alone, the compiler keeps a table of 84 MB in VMEM and a row
costs 6 ns; in the step most tables are read from HBM at 36-44 ns a row
(PERF.md section 6).  This chooses between variants, the traced step's
``moe_dispatch_device_ms`` is what counts.  Numbers from a CPU are no device
numbers.

    chiprun -- python3 tools/moe_dispatch_ab.py [cell ...]
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# cell: (tokens a pass, hidden, k, experts held, experts)
SHAPES = {
    "lfm_glm_update": (16384, 2048, 4, 8, 64),
    "glm_rollout": (4096, 2048, 4, 8, 64),
    "ling": (4096, 2560, 8, 8, 512),
}
REPEATS = 20
# how the weights' gradient is formed: as before PR 36; as shipped; the other placement
WEIGHT_GRADIENTS = ("k_gathers_of_ys", "scatter_R_scalars", "gather_kT_scalars")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from gymfx_tpu.train import mla_moe_decoder as mod

    device = jax.devices()[0].device_kind

    def timed(fn, *args):
        """Median milliseconds of a call, ``REPEATS`` calls in flight a reading."""
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        reads = []
        for _ in range(5):
            start = time.perf_counter()
            for _ in range(REPEATS):
                out = fn(*args)
            jax.block_until_ready(out)
            reads.append(1e3 * (time.perf_counter() - start) / REPEATS)
        return sorted(reads)[len(reads) // 2]

    def token_order(plan):
        """(buffer row, choice number, rest of its token's run) at every position of the
        held choices placed token-major, and each token's (first position, count)."""
        k, tokens = plan.dest.shape
        rows = plan.valid.shape[0]
        count = jnp.sum(plan.held, axis=0, dtype=jnp.int32)
        first = jnp.cumsum(count, dtype=jnp.int32) - count
        ahead = jnp.cumsum(plan.held, axis=0, dtype=jnp.int32) - plan.held
        place = jnp.where(plan.held, first + ahead, rows)
        placed = jnp.zeros((rows, 3), jnp.int32).at[place.reshape(-1)].set(
            jnp.stack([plan.dest, jnp.arange(k * tokens, dtype=jnp.int32).reshape(k, tokens),
                       count - ahead], axis=-1).reshape(-1, 3), mode="drop")
        return placed[:, 0], placed[:, 1], placed[:, 2], first, count

    def sum_in_token_order(rows, plan, weights=None):
        at, number, rest, first, count = token_order(plan)
        ordered = mod._rows_of(rows, at, rest > 0)
        if weights is not None:
            ordered = ordered * jnp.take(weights.reshape(-1), number, mode="clip")[:, None]
        run = ordered
        for ahead in range(1, plan.dest.shape[0]):
            neighbour = jnp.pad(ordered[ahead:], ((0, ahead), (0, 0)))
            run = run + jnp.where((rest > ahead)[:, None], neighbour, 0)
        return mod._rows_of(run, first, count > 0)

    # the way back, (rows, plan, weights or None) -> (T, hidden), by form
    sums = {"k_gathers": mod._sum_of_choices, "token_order": sum_in_token_order}

    def way_back(form, how):
        """(rows to the buffer, weighted sum back) with the way back ``form`` and the
        weights' gradient ``how``; the module's own where both are what it ships."""
        if (form, how) == ("k_gathers", "scatter_R_scalars"):
            return mod.dispatch_rows, mod.combine_rows

        back = sums[form]

        @jax.custom_vjp
        def there(y, plan):
            return mod.dispatch_rows(y, plan)

        there.defvjp(lambda y, plan: (there(y, plan), plan),
                     lambda plan, g: (back(g, plan).astype(g.dtype), None))

        @jax.custom_vjp
        def combine(ys, weights, plan):
            return back(ys, plan, weights.astype(ys.dtype)).astype(ys.dtype)

        def bwd(res, g):
            ys, weights, plan = res
            g_ys, g_weights, _ = mod._combine_bwd(res, g)
            if how == "k_gathers_of_ys":
                g_weights = jnp.stack([
                    jnp.sum(mod._rows_of(ys, plan.dest[j], plan.held[j]).astype(jnp.float32)
                            * g.astype(jnp.float32), axis=-1) for j in range(plan.dest.shape[0])])
            elif how == "gather_kT_scalars":
                dots = jnp.sum(ys.astype(jnp.float32) * jnp.take(
                    g, plan.token, axis=0, mode="clip").astype(jnp.float32), axis=-1)
                g_weights = jnp.where(plan.held, jnp.take(dots, plan.dest, mode="clip"), 0)
            return g_ys, g_weights.astype(weights.dtype), None

        combine.defvjp(lambda ys, weights, plan: (combine(ys, weights, plan), (ys, weights, plan)),
                       bwd)
        return there, combine

    for cell in argv[1:] or SHAPES:
        tokens, hidden, k, held, n = SHAPES[cell]
        dims = mod.Dims(hidden_size=hidden, num_experts_per_tok=k, experts_held=held,
                        n_routed_experts=n)
        align = mod.tile_rows_for(tokens, dims)
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        idx = jax.lax.top_k(jax.random.uniform(keys[0], (tokens, n)), k)[1]
        y = jax.random.normal(keys[1], (tokens, hidden)).astype(jnp.bfloat16)
        weights = jax.random.uniform(keys[2], (tokens, k), jnp.float32, 0.1, 1.0)
        probe = jax.random.normal(keys[3], (tokens, hidden), jnp.float32)
        for buffer in ("short", "worst"):
            rows = mod.buffer_rows(tokens, dims, align, worst=buffer == "worst")
            ys = jax.random.normal(keys[4], (rows, hidden)).astype(jnp.bfloat16)
            plan_of = lambda i: mod.routing_plan(i, dims, align, rows)  # noqa: E731
            plan = jax.jit(plan_of)(idx)
            w16 = weights.T.astype(ys.dtype)
            base = {"cell": cell, "tokens": tokens, "hidden": hidden, "k": k, "held": held,
                    "experts": n, "buffer": buffer, "rows": rows, "tile_rows": align,
                    "device": device,
                    "fits_short": bool(mod.fits_short_buffer(idx, dims, align))}
            got = {}
            for form, total in sums.items():
                def forward(y, weights, idx, how="scatter_R_scalars", form=form):
                    there, combine = way_back(form, how)
                    plan = plan_of(idx)
                    return combine(there(y, plan), weights.T, plan)

                def both(y, weights, idx, probe, how):
                    return jax.grad(lambda y, w: jnp.sum(
                        forward(y, w, idx, how).astype(jnp.float32) * probe), (0, 1))(y, weights)

                got[form] = jax.jit(total)(ys, plan, w16).astype(jnp.float32)
                row = {**base, "form": form,
                       "plan_ms": timed(plan_of, idx) if form == "k_gathers" else timed(
                           lambda i: (plan_of(i), token_order(plan_of(i))), idx),
                       "sum_ms": timed(total, ys, plan, w16),
                       "forward_ms": timed(forward, y, weights, idx)}
                for how in WEIGHT_GRADIENTS:
                    row[f"forward_backward_ms.{how}"] = timed(
                        lambda *a, how=how: both(*a, how), y, weights, idx, probe)
                print(json.dumps(row), flush=True)
            apart = jnp.abs(got["k_gathers"] - got["token_order"])
            print(json.dumps({
                **base, "the_two_sums_max_abs_diff": float(apart.max()),
                "elements_apart": int((apart > 0).sum()),
                "largest_sum": float(jnp.abs(got["k_gathers"]).max()),
                "rows_there_ms.zeroed": timed(
                    lambda y, plan: mod._rows_of(y, plan.token, plan.valid), y, plan),
                "rows_there_ms.as_they_come": timed(mod.dispatch_rows, y, plan)}), flush=True)
        # for the record: what a row costs by its element type
        at = jax.random.randint(keys[0], (tokens,), 0, rows)
        words = jax.lax.bitcast_convert_type(
            ys.reshape(rows, hidden // 2, 2), jnp.uint32)
        take = lambda table, at: jnp.take(table, at, axis=0, mode="clip")  # noqa: E731
        print(json.dumps({"cell": cell, "rows_gathered": tokens, "of": rows, "device": device,
                          "rows_ms.bfloat16": timed(take, ys, at),
                          "rows_ms.uint32": timed(take, words, at)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
