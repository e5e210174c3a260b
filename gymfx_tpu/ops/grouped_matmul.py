"""Pallas TPU kernel: grouped matrix product over the experts a chip holds.

``out[r] = lhs[r] @ rhs[group of r]`` for rows sorted by group, where every
group's rows start at a multiple of ``tile_rows`` (``train/mla_moe_decoder.py
::routing_plan`` pads them so): a tile of rows then belongs to ONE group,
and the kernel is a tiled matmul whose weight block is picked by a
prefetched ``tile -> group`` table.  The row buffer is sized for the worst
case of a dropless expert layer (every choice of every token on an expert
held here), most of it unused on an ordinary batch: tiles past the last
used one are skipped, and their block indices repeat the last used tile's
so that nothing is fetched or written for them.  Their output rows are
never written: callers mask them (``combine_rows`` reads held rows only).

The grid runs the row tiles INNERMOST: consecutive tiles of one expert
keep its weight block in VMEM, so each expert's weights are read once per
column block.  Backward: ``d lhs`` is the same kernel against the
transposed weights (contracted in the kernel, no transposed copy);
``d rhs[g] = lhs_g^T @ d out_g`` is a second kernel that walks the row
tiles as its reduction axis and writes a group's block when its last tile
is done (every group has at least one tile, so every block is written).

``interpret=None`` resolves in ``ops/dispatch.py``: compiled on a TPU, the
Pallas interpreter elsewhere.  ``jax.lax.ragged_dot`` computes the same (the
tests hold the kernel against it); on the chip it lost the A/B (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gymfx_tpu.ops.dispatch import resolve_interpret
from gymfx_tpu.telemetry.scopes import KERNEL_GROUPED_MATMUL, KERNEL_GROUPED_MATMUL_DW

_VMEM_LIMIT = 64 * 1024 * 1024


def tile_groups(group_sizes, rows: int, tile_rows: int):
    """(group of each tile (rows // tile_rows,), number of tiles in use (1,))
    for groups whose sizes are multiples of ``tile_rows``."""
    ends = jnp.cumsum(group_sizes)
    first_row = jnp.arange(rows // tile_rows, dtype=jnp.int32) * tile_rows
    group = jnp.searchsorted(ends, first_row, side="right").astype(jnp.int32)
    used = (ends[-1] // tile_rows).astype(jnp.int32)
    return jnp.minimum(group, group_sizes.shape[0] - 1), used.reshape(1)


def _column_block(n: int, limit: int) -> int:
    for block in (1024, 768, 512, 384, 256, 128):
        if block <= limit and n % block == 0:
            return block
    return n


def _matmul_kernel(group_ref, used_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del group_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract, preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def _matmul(lhs, rhs, group, used, *, tile_rows, transpose_rhs, interpret):
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_block(n, 512)

    def tile(i, used_ref):
        return jnp.minimum(i, used_ref[0] - 1)

    rhs_block = (1, tn, k) if transpose_rhs else (1, k, tn)

    def rhs_index(j, i, group_ref, used_ref):
        g = group_ref[tile(i, used_ref)]
        return (g, j, 0) if transpose_rhs else (g, 0, j)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, k), lambda j, i, g, u: (tile(i, u), 0)),
                pl.BlockSpec(rhs_block, rhs_index),
            ],
            out_specs=pl.BlockSpec((tile_rows, tn), lambda j, i, g, u: (tile(i, u), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_GROUPED_MATMUL,
    )(group, used, lhs, rhs)


def _dw_kernel(group_ref, used_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    i, used = pl.program_id(2), used_ref[0]
    here = group_ref[jnp.minimum(i, used - 1)]
    first = (i == 0) | (group_ref[jnp.maximum(i, 1) - 1] != here)
    last = (i == used - 1) | (group_ref[jnp.minimum(i + 1, used - 1)] != here)

    @pl.when(i < used)
    def _():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _dw(lhs, dout, group, used, *, groups, tile_rows, interpret):
    """``out[g] = lhs_g^T @ dout_g``: (groups, k, n)."""
    rows, k = lhs.shape
    n = dout.shape[1]
    tk, tn = _column_block(k, 1024), _column_block(n, 1024)

    def tile(i, used_ref):
        return jnp.minimum(i, used_ref[0] - 1)

    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, tk), lambda a, b, i, g, u: (tile(i, u), a)),
                pl.BlockSpec((tile_rows, tn), lambda a, b, i, g, u: (tile(i, u), b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, g, u: (g[tile(i, u)], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_GROUPED_MATMUL_DW,
    )(group, used, lhs, dout)


@functools.lru_cache(maxsize=None)
def _make(tile_rows: int, interpret: bool):
    @jax.custom_vjp
    def product(lhs, rhs, group, used):
        return _matmul(lhs, rhs, group, used, tile_rows=tile_rows,
                       transpose_rhs=False, interpret=interpret)

    def fwd(lhs, rhs, group, used):
        return product(lhs, rhs, group, used), (lhs, rhs, group, used)

    def bwd(res, dout):
        lhs, rhs, group, used = res
        dlhs = _matmul(dout, rhs, group, used, tile_rows=tile_rows,
                       transpose_rhs=True, interpret=interpret)
        drhs = _dw(lhs, dout, group, used, groups=rhs.shape[0],
                   tile_rows=tile_rows, interpret=interpret)
        return dlhs, drhs, None, None

    product.defvjp(fwd, bwd)
    return product


def grouped_matmul(lhs, rhs, group, used, *, tile_rows: int,
                   interpret: bool | None = None):
    """``lhs`` (rows, k) sorted by group with tile-aligned groups, ``rhs``
    (groups, k, n), ``group``/``used`` from :func:`tile_groups` -> (rows, n)
    in ``lhs``'s dtype.  Rows past the tiles in use are NOT written.
    Differentiable in ``lhs`` and ``rhs``."""
    if lhs.shape[0] % tile_rows:
        raise ValueError(f"{lhs.shape[0]} rows are no multiple of the tile, {tile_rows}")
    return _make(int(tile_rows), resolve_interpret(interpret))(lhs, rhs, group, used)
