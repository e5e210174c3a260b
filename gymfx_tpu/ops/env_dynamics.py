"""Pallas TPU kernels: fused per-step env dynamics (broker + reward).

The bar venue's hot loop spends its non-GEMM time in two chains of
small elementwise ops over per-env ledger scalars, each materializing
(envs,)-wide intermediates in HBM dozens of times per step:

  A. ``broker.fill_pending`` -> ``broker.check_brackets`` (+ the FX
     financing accrual) — the order/bracket chain at the bar open;
  B. ``broker.mark_to_market`` -> ``rewards.compute_reward`` — the
     equity mark + reward at the bar close.

The strategy kernel sits between the two in ``core/env.step``, so the
family is TWO env-blocked pallas VMEM passes bracketing it (not one) —
no reordering of the XLA program, which is what keeps the parity
argument trivial.  Each kernel packs the touched ``EnvState`` scalars
into (n_fields, rows, 128) faces — one field per leading index, the env
batch folded over (rows, lanes) so every field is a whole-vreg tile;
the (env_block, n_fields) layout with column slices and a scatter-add
diag bump was refused by Mosaic (PR 22) — runs THE SAME ``core/broker`` /
``core/rewards`` functions elementwise on the block (op-for-op the XLA
path, including the ``advance``/``mark`` select gating), and repacks.
The plain-XLA path stays the bitwise oracle
(tests/test_env_dynamics_kernel.py), exactly like
``ops/window_zscore.fused_step_obs``.

The trainers' per-env ``vmap`` folds into the grid via
``jax.custom_batching.custom_vmap`` (the fused-obs pattern).  Dispatch
lives in ``core/env.step`` behind the ``rollout_env_kernel`` knob, whose
off|on|interpret modes resolve in ``ops/dispatch.py`` ("on" = this
kernel compiled on a TPU, or its error; the XLA twin on a CPU); EnvConfig validation rejects
configurations the packed-scalar form cannot reproduce (LOB venue,
sharpe's ring buffer, f64 oracle mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gymfx_tpu.core import broker, rewards
from gymfx_tpu.ops.dispatch import resolve_interpret
from gymfx_tpu.telemetry.scopes import KERNEL_FILL_BRACKETS, KERNEL_MARK_REWARD
from gymfx_tpu.core.types import (
    EXEC_DIAG_INDEX,
    EXEC_DIAG_KEYS,
    EnvConfig,
    EnvParams,
    EnvState,
)

_DENIED_IDX = EXEC_DIAG_INDEX["order_denied_min_quantity"]

# EnvState scalars read/written by fill_pending + check_brackets (via
# apply_fill).  Order is the packing contract between the dispatch
# wrappers and the kernel bodies.
FILL_FLOAT_FIELDS = (
    "pos", "entry_price", "cash_delta", "commission_paid",
    "last_trade_cost", "trade_pnl_sum", "trade_pnl_sumsq",
    "open_trade_commission", "pending_target", "pending_sl",
    "pending_tp", "bracket_sl", "bracket_tp",
)
FILL_BOOL_FIELDS = ("pending_active", "pending_forced")
FILL_INT_FIELDS = ("trade_count", "trades_won", "trades_lost")
# params consumed by the fill/bracket chain, packed as a broadcast row
FILL_PARAM_FIELDS = (
    "slippage", "commission", "price_tick", "size_step", "min_qty",
)

# EnvState scalars read/written by mark_to_market + compute_reward
MARK_FLOAT_FIELDS = (
    "pos", "cash_delta", "equity_delta", "prev_equity_delta",
    "peak_equity_delta", "max_drawdown_money", "max_drawdown_pct",
    "reward_peak",
)
MARK_OUT_FIELDS = (
    "equity_delta", "prev_equity_delta", "peak_equity_delta",
    "max_drawdown_money", "max_drawdown_pct", "reward_peak",
)
MARK_PARAM_FIELDS = ("initial_cash", "reward_scale", "penalty_lambda")


def _select(pred, a: EnvState, b: EnvState) -> EnvState:
    """core/env._select for the block state.  Mosaic has no select on
    mask (i1) vectors, so bool fields take the equivalent logic form."""

    def pick(x, y):
        if x.dtype == jnp.bool_:
            return broker.pick_mask(pred, x, y)
        return jnp.where(pred, x, y)

    return EnvState(*(pick(x, y) for x, y in zip(a, b)))


def _block_state(float_cols, bool_cols, int_cols, face) -> EnvState:
    """An EnvState whose listed fields are ``face``-shaped (rows, lanes)
    env tiles and whose untouched fields are typed dummies — the
    broker/reward functions never read the dummies, and ``_select`` zips
    over all of them harmlessly (where(pred, 0, 0))."""
    zf = jnp.zeros(face, jnp.float32)
    zi = jnp.zeros(face, jnp.int32)
    zb = zi != 0
    fields = {}
    for name in EnvState._fields:
        if name in ("started", "terminated", "pending_active",
                    "pending_forced"):
            fields[name] = zb
        elif name in ("t", "termination_reason", "trade_count",
                      "trades_won", "trades_lost", "reward_buffer_len",
                      "reward_buffer_idx", "tr_len", "tr_idx",
                      "last_coerced_action"):
            fields[name] = zi
        elif name == "exec_diag":
            # (n_counters, *face): broker.bump_exec_diag's one-hot add
            # over axis 0 works elementwise across the env tile
            fields[name] = jnp.zeros((len(EXEC_DIAG_KEYS), *face), jnp.int32)
        elif name == "action_diag":
            fields[name] = jnp.zeros((1, *face), jnp.int32)
        else:
            fields[name] = zf
    fields.update(float_cols)
    for name, col in bool_cols.items():
        fields[name] = col != 0
    fields.update(int_cols)
    return EnvState(**fields)


def _dummy_params(cols) -> EnvParams:
    z = jnp.zeros((), jnp.float32)
    fields = {name: z for name in EnvParams._fields}
    fields["user"] = ()
    fields.update(cols)
    return EnvParams(**fields)


# ---------------------------------------------------------------------------
# Kernel A: fill_pending + check_brackets (+ financing accrual)
#
# Layout: every packed array is (fields, rows, 128) — one field per
# leading index, the env batch folded into (rows, lanes) faces, so each
# field of a block is a whole-vreg (rb, 128) tile and the broker chain
# runs as plain elementwise VPU ops.  Params are SMEM scalars.
# ---------------------------------------------------------------------------
def _fill_bracket_kernel(pp_ref, fl_ref, it_ref, bars_ref, out_f_ref,
                         out_i_ref, *, cfg: EnvConfig):
    face = fl_ref.shape[1:]
    float_cols = {n: fl_ref[i] for i, n in enumerate(FILL_FLOAT_FIELDS)}
    nb = len(FILL_BOOL_FIELDS)
    bool_cols = {n: it_ref[i] for i, n in enumerate(FILL_BOOL_FIELDS)}
    int_cols = {
        n: it_ref[nb + i] for i, n in enumerate(FILL_INT_FIELDS)
    }
    advance = it_ref[nb + len(FILL_INT_FIELDS)] != 0
    st = _block_state(float_cols, bool_cols, int_cols, face)
    params = _dummy_params(
        {n: pp_ref[i] for i, n in enumerate(FILL_PARAM_FIELDS)}
    )
    o, h, l, c = bars_ref[0], bars_ref[1], bars_ref[2], bars_ref[3]

    # op-for-op the core/env.step bar-venue advance (steps 1, 2, 2b)
    st_f = broker.fill_pending(st, o, params, cfg, h, l)
    st = _select(advance, st_f, st)
    st_b = broker.check_brackets(st, o, h, l, cfg, params)
    st = _select(advance, st_b, st)
    if cfg.financing_enabled:
        accrual = st.pos * c * bars_ref[4]
        st = st._replace(
            cash_delta=st.cash_delta + jnp.where(advance, accrual, 0.0)
        )

    for i, n in enumerate(FILL_FLOAT_FIELDS):
        out_f_ref[i] = getattr(st, n)
    out_i = (
        [getattr(st, n).astype(jnp.int32) for n in FILL_BOOL_FIELDS]
        + [getattr(st, n) for n in FILL_INT_FIELDS]
        + [st.exec_diag[_DENIED_IDX]]
    )
    for i, col in enumerate(out_i):
        out_i_ref[i] = col


# ---------------------------------------------------------------------------
# Kernel B: mark_to_market + compute_reward
# ---------------------------------------------------------------------------
def _mark_reward_kernel(pp_ref, fl_ref, it_ref, out_ref, *,
                        cfg: EnvConfig):
    face = fl_ref.shape[1:]
    float_cols = {n: fl_ref[i] for i, n in enumerate(MARK_FLOAT_FIELDS)}
    close = fl_ref[len(MARK_FLOAT_FIELDS)]
    mark_pred = it_ref[0] != 0
    live = it_ref[1] != 0
    st = _block_state(float_cols, {}, {}, face)
    params = _dummy_params(
        {n: pp_ref[i] for i, n in enumerate(MARK_PARAM_FIELDS)}
    )

    # op-for-op core/env.step step 4 + the reward block
    st_m = broker.mark_to_market(st, close, params)
    st = _select(mark_pred, st_m, st)
    st, base_reward = rewards.compute_reward(st, cfg, params, live)

    for i, n in enumerate(MARK_OUT_FIELDS):
        out_ref[i] = getattr(st, n)
    out_ref[len(MARK_OUT_FIELDS)] = base_reward


# ---------------------------------------------------------------------------
# batched pallas dispatch + custom_vmap plumbing
# ---------------------------------------------------------------------------
_LANES = 128
_MAX_BLOCK_ROWS = 64    # 64 x 128 = 8192 envs per program


def _fold(x, rows: int):
    """(B, F) per-env rows -> (F, rows, 128) field faces, zero-padded
    (a zero env is inert: ``advance``/``mark_pred`` are 0 there and the
    tail is sliced away by :func:`_unfold`)."""
    b, f = x.shape
    x = jnp.pad(x, ((0, rows * _LANES - b), (0, 0)))
    return x.T.reshape(f, rows, _LANES)


def _unfold(y, b: int):
    """(F, rows, 128) field faces -> (B, F) per-env rows."""
    return y.reshape(y.shape[0], -1).T[:b]


def _rows(batch: int):
    """(rows, block_rows) of the folded env batch: rows pad to the f32
    sublane tile (8); a block is the largest divisor up to
    ``_MAX_BLOCK_ROWS`` (a few dozen f32 faces of 32 KiB — VMEM never
    binds)."""
    rows = -(-batch // (8 * _LANES)) * 8
    rb = next(r for r in (_MAX_BLOCK_ROWS, 32, 16, 8) if rows % r == 0)
    return rows, rb


def _face_call(kernel, name, ins, out_fields, out_dtypes, pp, rb, interpret):
    """One pallas_call over (fields, rows, 128) faces, gridded over row
    blocks; ``pp`` rides whole in SMEM as the scalar-params vector.
    ``name`` is the kernel's stable name in the compiled program and in a
    device trace (telemetry/scopes.py)."""
    rows = ins[0].shape[1]

    def face(f):
        return pl.BlockSpec((f, rb, _LANES), lambda i: (0, i, 0))

    return pl.pallas_call(
        kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [face(x.shape[0]) for x in ins],
        out_specs=[face(f) for f in out_fields],
        out_shape=[
            jax.ShapeDtypeStruct((f, rows, _LANES), d)
            for f, d in zip(out_fields, out_dtypes)
        ],
        interpret=interpret,
        name=name,
    )(pp, *ins)


def _broadcast_unbatched(axis_size, in_batched, args):
    return tuple(
        x if bat else jnp.broadcast_to(x[None], (axis_size, *x.shape))
        for x, bat in zip(args, in_batched)
    )


@functools.lru_cache(maxsize=None)
def _make_fill_bracket(cfg: EnvConfig, interpret: bool):
    from jax.custom_batching import custom_vmap

    nf = len(FILL_FLOAT_FIELDS)
    ni = len(FILL_BOOL_FIELDS) + len(FILL_INT_FIELDS) + 1
    kernel = functools.partial(_fill_bracket_kernel, cfg=cfg)

    def batched(fl, it, bars, pp):           # (B, NF) (B, NI) (B, 5) (NP,)
        b = fl.shape[0]
        rows, rb = _rows(b)
        out_f, out_i = _face_call(
            kernel, KERNEL_FILL_BRACKETS,
            [_fold(x, rows) for x in (fl, it, bars)],
            (nf, ni), (jnp.float32, jnp.int32), pp, rb, interpret,
        )
        return _unfold(out_f, b), _unfold(out_i, b)

    @custom_vmap
    def one(fl, it, bars, pp):               # (NF,), (NI,), (5,), (NP,)
        out_f, out_i = batched(fl[None], it[None], bars[None], pp)
        return out_f[0], out_i[0]

    @one.def_vmap
    def _rule(axis_size, in_batched, fl, it, bars, pp):
        fl, it, bars, pp = _broadcast_unbatched(
            axis_size, in_batched, (fl, it, bars, pp)
        )
        # params are identical across envs: one scalar row
        return batched(fl, it, bars, pp[0]), (True, True)

    return one


@functools.lru_cache(maxsize=None)
def _make_mark_reward(cfg: EnvConfig, interpret: bool):
    from jax.custom_batching import custom_vmap

    no = len(MARK_OUT_FIELDS) + 1
    kernel = functools.partial(_mark_reward_kernel, cfg=cfg)

    def batched(fl, it, pp):                 # (B, NF + 1) (B, 2) (NP,)
        b = fl.shape[0]
        rows, rb = _rows(b)
        (out,) = _face_call(
            kernel, KERNEL_MARK_REWARD, [_fold(x, rows) for x in (fl, it)],
            (no,), (jnp.float32,), pp, rb, interpret,
        )
        return _unfold(out, b)

    @custom_vmap
    def one(fl, it, pp):
        return batched(fl[None], it[None], pp)[0]

    @one.def_vmap
    def _rule(axis_size, in_batched, fl, it, pp):
        fl, it, pp = _broadcast_unbatched(
            axis_size, in_batched, (fl, it, pp)
        )
        return batched(fl, it, pp[0]), True

    return one


# ---------------------------------------------------------------------------
# public entry points (called from core/env.step)
# ---------------------------------------------------------------------------
def fused_fill_brackets(
    st: EnvState, o, h, l, c, accrual_rate, advance, cfg: EnvConfig,
    params: EnvParams, *, interpret: bool | None = None,
) -> EnvState:
    """Kernel A: the advance-gated fill/bracket/financing chain of
    ``core/env.step`` (steps 1, 2, 2b) as one VMEM pass.  Bitwise
    identical to the XLA path by construction (same functions, same
    select gating, packed per-env scalars)."""
    interpret = resolve_interpret(interpret)
    one = _make_fill_bracket(cfg, bool(interpret))
    d = st.pos.dtype
    fl = jnp.stack(
        [getattr(st, n).astype(jnp.float32) for n in FILL_FLOAT_FIELDS],
        axis=-1,
    )
    it = jnp.stack(
        [getattr(st, n).astype(jnp.int32) for n in FILL_BOOL_FIELDS]
        + [getattr(st, n) for n in FILL_INT_FIELDS]
        + [advance.astype(jnp.int32)],
        axis=-1,
    )
    accrual = (
        accrual_rate if accrual_rate is not None
        else jnp.zeros_like(jnp.asarray(o))
    )
    bars = jnp.stack(
        [jnp.asarray(x, jnp.float32) for x in (o, h, l, c, accrual)],
        axis=-1,
    )
    pp = jnp.stack(
        [getattr(params, n).astype(jnp.float32)
         for n in FILL_PARAM_FIELDS],
        axis=-1,
    )
    out_f, out_i = one(fl, it, bars, pp)
    updates = {
        n: out_f[..., i].astype(d)
        for i, n in enumerate(FILL_FLOAT_FIELDS)
    }
    nb = len(FILL_BOOL_FIELDS)
    for i, n in enumerate(FILL_BOOL_FIELDS):
        updates[n] = out_i[..., i] != 0
    for i, n in enumerate(FILL_INT_FIELDS):
        updates[n] = out_i[..., nb + i]
    denied = out_i[..., nb + len(FILL_INT_FIELDS)]
    updates["exec_diag"] = st.exec_diag.at[..., _DENIED_IDX].add(denied)
    return st._replace(**updates)


def fused_mark_reward(
    st: EnvState, c, mark_pred, live, cfg: EnvConfig, params: EnvParams,
    *, interpret: bool | None = None,
):
    """Kernel B: the mark/drawdown/reward chain of ``core/env.step``
    (step 4 + the reward block) as one VMEM pass.  Returns
    (new_state, base_reward); the reward carries are updated at the
    mark's program position — nothing between mark and reward in the
    XLA step reads or writes them, so the final state is identical."""
    interpret = resolve_interpret(interpret)
    one = _make_mark_reward(cfg, bool(interpret))
    d = st.pos.dtype
    fl = jnp.stack(
        [getattr(st, n).astype(jnp.float32) for n in MARK_FLOAT_FIELDS]
        + [jnp.asarray(c, jnp.float32)],
        axis=-1,
    )
    it = jnp.stack(
        [mark_pred.astype(jnp.int32), live.astype(jnp.int32)], axis=-1
    )
    pp = jnp.stack(
        [getattr(params, n).astype(jnp.float32)
         for n in MARK_PARAM_FIELDS],
        axis=-1,
    )
    out = one(fl, it, pp)
    updates = {
        n: out[..., i].astype(d) for i, n in enumerate(MARK_OUT_FIELDS)
    }
    base_reward = out[..., len(MARK_OUT_FIELDS)].astype(d)
    return st._replace(**updates), base_reward
