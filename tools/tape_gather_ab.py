"""Micro A/B of the env step's tape read: what one lookup by bar index costs
on the chip, per form of the table (ISSUE 27; the finding is in PERF.md).

Stand-alone: imports nothing of the package and is no switch in it.  At the
flagship cell's own shapes (a 132,480-bar tape, 131,072 envs, 64 lookups
inside one ``lax.scan``) every form reads the same four 32-bit columns at
one bar index per env, once with all indices equal (the cell: every env
starts at bar 0) and once with indices drawn uniformly
(``random_episode_start: true``).  Prints one JSON line per form and index
draw: milliseconds a lookup (median of ``--repeats`` timed calls, the scan's
own cost without any read printed beside it as ``none``) and the number of
``gather`` instructions in the compiled program.

    chiprun -- python tools/tape_gather_ab.py

A CPU run (``--allow-cpu``) rehearses the control flow only; its times are
not device times and are labelled with the platform they came from.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

LIVE = 4  # columns the step consumes of every row (open, high, low, close)


def _columns(n, k, seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(1.0, 2.0, size=(n, k)).astype(np.float32)


def _as_f32(words):
    return jax.lax.bitcast_convert_type(words, jnp.float32)


def make_forms(n):
    """name -> (table builder from the (n, 128) float32 columns, lookup of
    one scalar index returning the LIVE float32 columns)."""
    forms = {}

    forms["none"] = (lambda cols: (), None)

    # how a scalar index reaches the table.  "slice" is `tab[i]`, what the
    # parent's columns use: jnp wraps a negative index, clamps, and reads by
    # dynamic_slice, which vmap turns into a gather with one index component
    # PER DIMENSION of the table.  "clip" is jnp.take's explicit clamp and one
    # component; "bare" hands the gather the index as it is (StableHLO's
    # gather clamps a start index itself: `--oob` prints what the chip does).
    def fetch(tab, i, how):
        if how == "slice":
            return tab[i]
        if how == "clip":
            return jnp.take(tab, i, axis=0, mode="clip")
        if how == "barrier":
            return tab[jax.lax.optimization_barrier(i)]
        return tab.at[i].get(mode="promise_in_bounds")

    def cols_form(count, how="slice"):
        def build(cols):
            return tuple(jnp.asarray(cols[:, j]) for j in range(count))

        def look(tab, i):
            got = [fetch(c, i, how) for c in tab]
            return got + [got[0]] * (LIVE - len(got))

        return build, look

    forms["columns_x4"] = cols_form(4)   # the parent: one gather a column
    forms["columns_x1"] = cols_form(1)   # what ONE of them costs
    forms["columns_x4_bare"] = cols_form(4, "bare")
    forms["columns_x1_bare"] = cols_form(1, "bare")

    def row_form(k, how="slice", words=False, arith=False):
        def build(cols):
            tab = jnp.asarray(cols[:, :k])
            if words:
                tab = jax.lax.bitcast_convert_type(tab, jnp.uint32)
            return tab

        def look(tab, i):
            if arith:  # index arithmetic of the env step's kind, fused in
                i = jnp.where(i % 2 == 0, i, i + 1) - i % 2
            row = fetch(tab, i, how)
            if words:
                row = _as_f32(row)
            return [row[j] for j in range(LIVE)]

        return build, look

    for k in (4, 8, 16, 24, 32, 128):
        forms[f"row_k{k}"] = row_form(k)
    forms["row_k8_u32"] = row_form(8, words=True)
    forms["row_k24_u32"] = row_form(24, words=True)
    for k in (4, 8, 24, 32, 128):
        forms[f"row_k{k}_bare"] = row_form(k, "bare")
    forms["row_k8_clip"] = row_form(8, "clip")
    forms["row_k8_barrier"] = row_form(8, "barrier")
    forms["row_k8_bare_arith"] = row_form(8, "bare", arith=True)
    forms["row_k24_u32_bare"] = row_form(24, "bare", words=True)

    def transposed_form(k):
        def build(cols):
            return jnp.asarray(np.ascontiguousarray(cols[:, :k].T))

        def look(tab, i):
            col = tab[:, i]
            return [col[j] for j in range(LIVE)]

        return build, look

    forms["transposed_k4"] = transposed_form(4)
    forms["transposed_k8"] = transposed_form(8)

    def block_form(b, how="slice"):
        # blocks of b bars as full 128-lane rows: a row fetch of idx // b,
        # then a one-hot lane select of idx % b (uint32 words, so the sum
        # over the one live lane is exact for any bit pattern)
        k = 128 // b

        def build(cols):
            rows = -(-n // b)
            flat = np.zeros((rows * b, k), np.float32)
            flat[:n] = cols[:, :k]
            return jnp.asarray(flat.reshape(rows, 128).view(np.uint32))

        def look(tab, i):
            row = fetch(tab, i // b, how)
            lane = jnp.arange(128, dtype=jnp.int32)
            base = (i % b) * k
            got = [
                _as_f32(jnp.sum(jnp.where(lane == base + j, row, jnp.uint32(0))))
                for j in range(min(LIVE, k))
            ]
            return got + [got[0]] * (LIVE - len(got))

        return build, look

    # how the row reaches its consumers.  In the step program the columns
    # are materialised as 1-D arrays over the envs (an optimization barrier
    # stands for that here); the forms differ in how the (envs, k) gather
    # result becomes (k, envs): sliced column by column, transposed once, or
    # gathered with the offset dimension in front
    def batched_form(k, how):
        def build(cols):
            return jnp.asarray(cols[:, :k])

        def look_all(tab, idx):
            if how == "offset_first":
                dn = jax.lax.GatherDimensionNumbers(
                    offset_dims=(0,), collapsed_slice_dims=(0,),
                    start_index_map=(0,))
                cols = jax.lax.gather(
                    tab, idx[:, None], dn, slice_sizes=(1, k),
                    mode=jax.lax.GatherScatterMode.CLIP)
                got = [cols[j] for j in range(LIVE)]
            else:
                rows = tab.at[idx].get(mode="clip")
                if how == "transpose":
                    cols = rows.T
                    got = [cols[j] for j in range(LIVE)]
                else:
                    got = [rows[:, j] for j in range(LIVE)]
            return jax.lax.optimization_barrier(got)

        look_all.batched = True
        return build, look_all

    forms["row_k24_columns_barrier"] = batched_form(24, "slices")
    forms["row_k24_transpose_barrier"] = batched_form(24, "transpose")
    forms["row_k24_offset_first_barrier"] = batched_form(24, "offset_first")
    forms["row_k8_columns_barrier"] = batched_form(8, "slices")
    forms["row_k8_transpose_barrier"] = batched_form(8, "transpose")

    forms["blocks_b32"] = block_form(32)
    forms["blocks_b16"] = block_form(16)
    forms["blocks_b32_bare"] = block_form(32, "bare")
    return forms


def make_run(look, n, steps):
    def run(tab, idx):
        def body(carry, _):
            i, acc = carry
            if look is None:
                acc = acc + i.astype(jnp.float32)
            else:
                if getattr(look, "batched", False):
                    o, h, l, c = look(tab, i)
                else:
                    o, h, l, c = jax.vmap(lambda j: look(tab, j))(i)
                acc = acc + ((o + h) + (l + c))
            return (jnp.minimum(i + 1, n - 1), acc), None

        (i, acc), _ = jax.lax.scan(
            body, (idx, jnp.zeros(idx.shape, jnp.float32)), None, length=steps
        )
        return i, acc

    return jax.jit(run)


def probe(dev):
    """Out-of-bounds rows per way of indexing, and int32 patterns (negative,
    denormal-as-float, NaN payloads) through a float32 table and back."""
    n, k = 1000, 8
    tab = jnp.asarray(np.arange(n * k, dtype=np.float32).reshape(n, k))
    idx = jnp.asarray([-3, -1, 0, n - 1, n, n + 5], jnp.int32)
    rows = {
        "slice": jax.jit(jax.vmap(lambda i: tab[i]))(idx),
        "clip": jax.jit(lambda i: jnp.take(tab, i, axis=0, mode="clip"))(idx),
        "bare": jax.jit(jax.vmap(
            lambda i: tab.at[i].get(mode="promise_in_bounds")))(idx),
    }
    bits = np.array([-1, -2, 0, 1, 7, 10079, 0x7F800001, 0x7FC00001,
                     -0x400000, 0x00800000, -(2 ** 31), 2 ** 31 - 1], np.int32)
    ints = np.resize(bits, n)
    ftab = jnp.asarray(
        np.stack([ints.view(np.float32)] + [np.ones(n, np.float32)] * 7, 1))
    pick = jnp.arange(n, dtype=jnp.int32)
    back = jax.jit(jax.vmap(lambda i: jax.lax.bitcast_convert_type(
        ftab.at[i].get(mode="promise_in_bounds")[0], jnp.int32)))(pick)
    return {
        "probe": "oob_and_bits", "platform": dev.platform,
        "indices": [int(i) for i in idx],
        "row_returned": {
            how: [int(v) // k for v in np.asarray(r)[:, 0]]
            for how, r in rows.items()
        },
        "int32_bits_survive_float32_table": bool(
            np.array_equal(np.asarray(back), ints)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bars", type=int, default=132_480)
    ap.add_argument("--envs", type=int, default=131_072)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--forms", default="")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--oob", action="store_true",
                    help="also print what each way of indexing returns out "
                         "of bounds, and whether int32 bits survive a ride "
                         "in a float32 table")
    ap.add_argument("--out", default="chiprun_out/tape_gather_ab.jsonl")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(json.dumps({"error": f"no TPU (platform {dev.platform})"}))
        return 2

    n, envs, steps = args.bars, args.envs, args.steps
    cols = _columns(n, 128, args.seed)
    rng = np.random.default_rng(args.seed + 1)
    draws = {
        "equal": np.zeros(envs, np.int32),
        "uniform": rng.integers(0, n - steps, size=envs).astype(np.int32),
    }
    forms = make_forms(n)
    wanted = [f for f in args.forms.split(",") if f] or list(forms)
    want = None  # the parent's form's result: every other form must equal it
    lines = []
    for name in wanted:
        build, look = forms[name]
        tab = build(cols)
        run = make_run(look, n, steps)
        for draw, idx_host in draws.items():
            idx = jnp.asarray(idx_host)
            compiled = run.lower(tab, idx).compile()
            gathers = compiled.as_text().count(" gather(")
            out = compiled(tab, idx)
            jax.block_until_ready(out)
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                out = compiled(tab, idx)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
            acc = np.asarray(out[1])
            line = {
                "form": name, "indices": draw,
                "ms_per_lookup": statistics.median(times) * 1e3 / steps,
                "ms_per_lookup_min": min(times) * 1e3 / steps,
                "gathers": gathers,
                "platform": dev.platform, "device_kind": dev.device_kind,
                "bars": n, "envs": envs, "steps": steps,
            }
            if name == "columns_x4":
                want = dict(want or {}, **{draw: acc})
            elif look is not None and want and not name.startswith("columns_x1"):
                line["bitwise_equal_to_columns_x4"] = bool(
                    np.array_equal(acc.view(np.uint32), want[draw].view(np.uint32))
                )
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.oob:
        lines.append(probe(dev))
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        import os

        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
