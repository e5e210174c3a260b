"""Pallas TPU kernel: fused window gather + leakage-safe z-score + clip.

The feature-window observation is the reference's per-step hot spot
(reference preprocessor_plugins/feature_window_preprocessor.py:174-191:
slice + z-score over up to 256 history rows per step per env).  The
scan env already reduces that to an O(1) dynamic-slice + normalize; this
kernel covers the BATCHED form — materializing scaled windows for many
steps/envs at once (offline featurization, eval sweeps, replay-buffer
exports) — as one fused pass: for each requested step, DMA the window
rows from HBM into VMEM, normalize with that step's precomputed
scaler moments, clip, and write the scaled window.  One kernel instead
of gather + sub + div + clip materializing (B, w, F) intermediates in
HBM three times.

The PER-STEP variant (:func:`fused_step_obs`) covers the rollout hot
path: the env scan already carries this step's (window, F) rows in
VMEM-resident registers (``state.feat_window``), so there is no gather
to fuse — what the kernel removes is the sub / div / mask / clip /
nan_to_num chain each materializing an (envs, window, F) intermediate
in HBM every step.  A ``jax.custom_batching.custom_vmap`` rule folds
the trainers' per-env ``vmap`` into an env-blocked grid (the
``ops/fused_attention.py`` pattern), and the kernel body reproduces
``core/obs.scale_feature_window`` op for op, so the plain-XLA path
stays the bitwise parity oracle (tests/test_ops.py) and what a CPU runs
under ``rollout_obs_kernel=on``.

``interpret=None`` resolves in ``ops/dispatch.py``: compiled on a TPU,
the pallas interpreter elsewhere, so tests run on CPU.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gymfx_tpu.ops.dispatch import resolve_interpret


def _kernel(steps_ref, feat_hbm, mean_ref, std_ref, neutral_ref, out_ref,
            scratch, sem, *, window: int, clip: float):
    b = pl.program_id(0)
    start = steps_ref[b]
    copy = pltpu.make_async_copy(
        feat_hbm.at[pl.ds(start, window), :], scratch, sem
    )
    copy.start()
    copy.wait()
    win = scratch[:]
    # moments live whole in VMEM; pick this step's row dynamically
    mean = mean_ref[pl.ds(start, 1), :]  # (1, F)
    std = std_ref[pl.ds(start, 1), :]
    neutral = neutral_ref[pl.ds(start, 1), :][0, 0]
    scaled = jnp.where(neutral != 0, 0.0, (win - mean) / std)
    if clip > 0:
        scaled = jnp.clip(scaled, -clip, clip)
    out_ref[0] = scaled


@functools.partial(jax.jit, static_argnames=("window", "clip", "interpret"))
def batched_scaled_windows(
    padded_features,  # (n + window, F) float32
    feat_mean,        # (n + 1, F)
    feat_std,         # (n + 1, F)
    feat_neutral,     # (n + 1,) bool
    steps,            # (B,) int32 — window ends (exclusive) at row `step`
    *,
    window: int,
    clip: float = 10.0,
    interpret: bool | None = None,
):
    """Scaled feature windows for a batch of steps: (B, window, F)."""
    interpret = resolve_interpret(interpret)
    b = steps.shape[0]
    f = orig_f = padded_features.shape[-1]
    steps = steps.astype(jnp.int32)

    if window % 8 != 0:
        raise ValueError("window must be a multiple of 8 (TPU sublane tiling)")

    # Lane-align the feature axis: Mosaic DMA slices must be 128-aligned
    # on the last dimension.  Pad features/means with zeros and stds with
    # ones (benign division), slice the result back to F at the end.
    f_pad = max(128, -(-f // 128) * 128) if not interpret else f
    if f_pad != f:
        pad = ((0, 0), (0, f_pad - f))
        padded_features = jnp.pad(padded_features, pad)
        feat_mean = jnp.pad(feat_mean, pad)
        feat_std = jnp.pad(feat_std, pad, constant_values=1.0)
        f = f_pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # features stay in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # moments whole in VMEM
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, window, f), lambda i, steps_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((window, f), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_kernel, window=window, clip=float(clip))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, window, f), jnp.float32),
        interpret=interpret,
    )(steps, padded_features, feat_mean, feat_std,
      feat_neutral.astype(jnp.int32).reshape(-1, 1))
    return out[:, :, :orig_f]


def reference_scaled_windows(
    padded_features, feat_mean, feat_std, feat_neutral, steps, *, window, clip=10.0
):
    """Plain-XLA reference implementation (the parity oracle)."""

    def one(step):
        win = jax.lax.dynamic_slice(
            padded_features, (step, jnp.zeros((), step.dtype)),
            (window, padded_features.shape[-1]),
        )
        scaled = jnp.where(
            feat_neutral[step], 0.0, (win - feat_mean[step]) / feat_std[step]
        )
        if clip > 0:
            scaled = jnp.clip(scaled, -clip, clip)
        return scaled

    return jax.vmap(one)(steps.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Per-step rollout variant (core/obs.py `rollout_obs_kernel` knob)
# ---------------------------------------------------------------------------
def _step_obs_kernel(win_ref, mean_ref, std_ref, neutral_ref, mask_ref,
                     out_ref, *, has_mask: bool, clip: float):
    """One env block's scaled policy input, op-for-op the body of
    ``core/obs.scale_feature_window`` (neutral-zero -> binary
    passthrough -> clip -> nan_to_num -> f32) so the XLA path stays a
    bitwise oracle."""
    win = win_ref[...]                      # (eb, W, F)
    mean = mean_ref[...]                    # (eb, 1, F)
    std = std_ref[...]
    neutral = neutral_ref[...]              # (eb, 1, 1) int32, nonzero=neutral
    scaled = jnp.where(neutral != 0, 0.0, (win - mean) / std)
    if has_mask:
        # pallas kernels cannot capture array constants, so the static
        # binary mask rides in as a broadcast (1, 1, F) int32 input
        scaled = jnp.where(mask_ref[...] != 0, win, scaled)
    if clip > 0:
        scaled = jnp.clip(scaled, -clip, clip)
    scaled = jnp.nan_to_num(
        scaled, nan=0.0, posinf=clip or 0.0, neginf=-(clip or 0.0)
    )
    out_ref[...] = scaled.astype(jnp.float32)


def _step_obs_env_block(batch: int, window: int, features: int) -> int:
    """Envs per program: two (W, F) f32 faces (window in, scaled out)
    plus moments per env, within a few MB of VMEM."""
    per_env = (2 * window * features + 2 * features + 1) * 4
    budget = max(1, (4 * 1024 * 1024) // per_env)
    for eb in (16, 8, 4, 2, 1):
        if eb <= budget and batch % eb == 0:
            return eb
    return 1


def _step_obs_batched(win, mean, std, neutral, *, binary_mask, clip: float,
                      interpret: bool):
    """Fused scaling on (B, W, F) windows + (B, F) moments + (B,) flags."""
    b, w, f = win.shape
    eb = _step_obs_env_block(b, w, f)
    has_mask = any(binary_mask)
    mask = np.asarray(
        binary_mask if has_mask else (False,) * f, dtype=np.int32
    ).reshape(1, 1, f)
    kernel = functools.partial(
        _step_obs_kernel, has_mask=has_mask, clip=float(clip)
    )
    # every block spans its array's trailing dims ((W, F), (1, F), (1, 1))
    # so Mosaic needs no (8, 128) tiling and F needs no lane padding —
    # the fused_attention (S, D)-face precedent
    out = pl.pallas_call(
        kernel,
        grid=(b // eb,),
        in_specs=[
            pl.BlockSpec((eb, w, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((eb, 1, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((eb, 1, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((eb, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, f), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((eb, w, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, w, f), jnp.float32),
        interpret=interpret,
    )(
        win,
        mean.reshape(b, 1, f),
        std.reshape(b, 1, f),
        neutral.astype(jnp.int32).reshape(b, 1, 1),
        jnp.asarray(mask),
    )
    return out


@functools.lru_cache(maxsize=None)
def _make_step_obs(binary_mask, clip: float, interpret: bool):
    from jax.custom_batching import custom_vmap

    def batched(win, mean, std, neutral):
        return _step_obs_batched(
            win, mean, std, neutral,
            binary_mask=binary_mask, clip=clip, interpret=interpret,
        )

    @custom_vmap
    def one(win, mean, std, neutral):       # (W, F), (F,), (F,), ()
        return batched(
            win[None], mean[None], std[None], neutral[None]
        )[0]

    @one.def_vmap
    def _one_vmap_rule(axis_size, in_batched, win, mean, std, neutral):
        if not all(in_batched):
            win, mean, std, neutral = (
                x if bat else jnp.broadcast_to(x[None], (axis_size, *x.shape))
                for x, bat in zip((win, mean, std, neutral), in_batched)
            )
        return batched(win, mean, std, neutral), True

    return one


def fused_step_obs(win, mean, std, neutral, *, binary_mask=(), clip=10.0,
                   interpret: bool | None = None):
    """Per-env fused rollout observation: one (window, F) feature
    window + this step's scaler moments -> the scaled, masked, clipped
    policy input, in one VMEM pass.  The trainers' per-env ``vmap``
    folds into an env-blocked grid via custom_vmap (obs building is
    never differentiated — the update replays stored obs — so no
    custom_vjp is needed).  Bitwise-identical to
    ``core/obs.scale_feature_window`` (the parity oracle)."""
    interpret = resolve_interpret(interpret)
    one = _make_step_obs(
        tuple(bool(x) for x in binary_mask), float(clip), bool(interpret)
    )
    return one(win, mean, std, jnp.asarray(neutral))
