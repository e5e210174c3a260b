"""Pallas TPU kernel: fused int16 tick-delta tape decode.

The compressed data path (data/compress.py) stores every quantized
MarketData column as int16 deltas against a per-shard int32 base with an
f32 divisor sidecar.  This kernel materializes the f32 view for a whole
stacked block of columns in one pass — sign-extend, rebase, convert,
divide — instead of XLA materializing an int32 intermediate per column
in HBM.  The pure-XLA ``data/compress.decode_q16_ref`` is the bitwise
parity oracle (tests/test_data_compress.py) and the decode arithmetic is
pinned: ``(base_i32 + delta_i32) -> f32 / inv_f32``, elementwise, so the
kernel and oracle agree bit-for-bit on any backend.

Rows are blocked over a grid (whole-tape curriculum slabs can run to
hundreds of thousands of rows — far beyond one VMEM face); the column
axis pads to the int16 sublane tile and the divisor pads with ones, both
sliced back after the call.  ``interpret=None`` resolves in
``ops/dispatch.py`` (compiled on a TPU, the interpreter elsewhere); the
``data_compress=interpret`` knob forces the interpreter anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from gymfx_tpu.ops.dispatch import resolve_interpret

_ROW_BLOCK = 2048


def _decode_kernel(delta_ref, base_ref, inv_ref, out_ref):
    d = delta_ref[...].astype(jnp.int32)       # (C, RB) int16 -> i32
    b = base_ref[...].astype(jnp.int32)        # (C, 1)
    out_ref[...] = (b + d).astype(jnp.float32) / inv_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_q16_block(delta, base, inv, *, interpret: bool | None = None):
    """Fused decode of a stacked q16 block.

    ``delta`` (C, rows) int16, ``base`` (C,) int32, ``inv`` (C,) f32 ->
    (C, rows) f32 = ``(base + delta) / inv``, bitwise-identical to
    ``decode_q16_ref``.
    """
    interpret = resolve_interpret(interpret)
    c, rows = delta.shape
    base2 = base.reshape(c, 1).astype(jnp.int32)
    inv2 = inv.reshape(c, 1).astype(jnp.float32)
    if interpret:
        c_pad, rb = c, rows
    else:
        # int16 sublane tile is 16; lane-align and block the row axis so
        # arbitrarily long slabs never exceed one VMEM face
        c_pad = -(-c // 16) * 16
        rb = min(_ROW_BLOCK, -(-rows // 128) * 128)
    rows_pad = -(-rows // rb) * rb
    if c_pad != c or rows_pad != rows:
        delta = jnp.pad(delta, ((0, c_pad - c), (0, rows_pad - rows)))
        base2 = jnp.pad(base2, ((0, c_pad - c), (0, 0)))
        # padded divisors are 1.0: benign division in the dead lanes
        inv2 = jnp.pad(inv2, ((0, c_pad - c), (0, 0)), constant_values=1.0)
    out = pl.pallas_call(
        _decode_kernel,
        grid=(rows_pad // rb,),
        in_specs=[
            pl.BlockSpec((c_pad, rb), lambda i: (0, i)),
            pl.BlockSpec((c_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((c_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((c_pad, rb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c_pad, rows_pad), jnp.float32),
        interpret=interpret,
    )(delta, base2, inv2)
    return out[:c, :rows]
