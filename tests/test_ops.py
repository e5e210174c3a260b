"""Pallas window z-score kernel vs the XLA reference implementation."""
import numpy as np
import pytest

from gymfx_tpu.data.feed import _build_feature_tensors
from gymfx_tpu.ops.window_zscore import (
    batched_scaled_windows,
    reference_scaled_windows,
)


def _tensors(n=200, f=3, w=16, sw=64, seed=0):
    import pandas as pd

    rng = np.random.default_rng(seed)
    df = pd.DataFrame(
        rng.normal(size=(n, f)) * [1.0, 30.0, 1e-2], columns=list("abc")
    )
    return _build_feature_tensors(
        df, feature_columns=("a", "b", "c"), window_size=w,
        scaling="rolling_zscore", scaling_window=sw,
    )


def test_kernel_matches_reference_impl():
    import jax.numpy as jnp

    w = 16
    padded, mean, std, neutral = _tensors(w=w)
    steps = jnp.asarray([0, 1, 5, 17, 63, 64, 65, 120, 199, 200], jnp.int32)
    args = (
        jnp.asarray(padded), jnp.asarray(mean), jnp.asarray(std),
        jnp.asarray(neutral), steps,
    )
    ours = batched_scaled_windows(*args, window=w, clip=10.0, interpret=True)
    ref = reference_scaled_windows(*args, window=w, clip=10.0)
    assert ours.shape == (10, w, 3)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-6)


def test_kernel_matches_manual_formula_and_clip():
    import jax.numpy as jnp

    w = 8
    padded, mean, std, neutral = _tensors(w=w, sw=32)
    step = 50
    out = batched_scaled_windows(
        jnp.asarray(padded), jnp.asarray(mean), jnp.asarray(std),
        jnp.asarray(neutral), jnp.asarray([step], jnp.int32),
        window=w, clip=1.5, interpret=True,
    )
    manual = (padded[step:step + w] - mean[step]) / std[step]
    manual = np.clip(manual, -1.5, 1.5)
    np.testing.assert_allclose(np.asarray(out[0]), manual, atol=1e-6)
    assert np.max(np.asarray(out)) <= 1.5


def test_neutral_steps_produce_zero_windows():
    import jax.numpy as jnp

    w = 8
    padded, mean, std, neutral = _tensors(w=w)
    out = batched_scaled_windows(
        jnp.asarray(padded), jnp.asarray(mean), jnp.asarray(std),
        jnp.asarray(neutral), jnp.asarray([0, 1], jnp.int32),
        window=w, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# fused per-step obs kernel (ops/window_zscore.fused_step_obs, r6):
# the rollout hot-path variant — one env's (W, F) window + this step's
# moments -> the scaled policy input, pinned BITWISE against the
# plain-XLA oracle core/obs.scale_feature_window
# ---------------------------------------------------------------------------
class _ObsCfg:
    def __init__(self, binary_mask=(), feature_clip=10.0):
        self.binary_mask = tuple(binary_mask)
        self.feature_clip = feature_clip


def _step_obs_case(b=6, w=16, f=3, seed=0):
    """Batched windows/moments with every edge the scaler handles:
    NaN features, a zero-std column (inf -> clip), neutral rows."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    win = rng.normal(size=(b, w, f)).astype(np.float32)
    win[0, 0, 0] = np.nan
    mean = rng.normal(size=(b, f)).astype(np.float32)
    std = np.abs(rng.normal(size=(b, f))).astype(np.float32) + 0.1
    std[1, 2] = 0.0                      # inf path -> posinf/neginf fill
    neutral = np.zeros(b, dtype=bool)
    neutral[2] = True
    return (jnp.asarray(win), jnp.asarray(mean), jnp.asarray(std),
            jnp.asarray(neutral))


@pytest.mark.parametrize("mask,clip", [
    ((), 10.0),
    ((False, True, False), 1.5),         # binary passthrough + tight clip
    ((), 0.0),                           # clip disabled
])
def test_fused_step_obs_bitwise_matches_oracle(mask, clip):
    import jax

    from gymfx_tpu.core.obs import scale_feature_window
    from gymfx_tpu.ops.window_zscore import fused_step_obs

    win, mean, std, neutral = _step_obs_case()
    cfg = _ObsCfg(binary_mask=mask or (False,) * 3, feature_clip=clip)
    ref = jax.vmap(
        lambda w_, m_, s_, n_: scale_feature_window(w_, m_, s_, n_, cfg)
    )(win, mean, std, neutral)
    # vmapped: the custom_vmap rule folds envs into the blocked grid
    ours = jax.vmap(
        lambda w_, m_, s_, n_: fused_step_obs(
            w_, m_, s_, n_, binary_mask=cfg.binary_mask,
            clip=cfg.feature_clip, interpret=True,
        )
    )(win, mean, std, neutral)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(ref))
    # unvmapped single env (batch-of-1 kernel path)
    one = fused_step_obs(
        win[0], mean[0], std[0], neutral[0],
        binary_mask=cfg.binary_mask, clip=cfg.feature_clip, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(one), np.asarray(ref[0]))


def test_fused_step_obs_vmap_broadcasts_unbatched_moments():
    """in_axes=(0, None, None, None): the def_vmap rule must broadcast
    the shared moments across the env axis."""
    import jax

    from gymfx_tpu.core.obs import scale_feature_window
    from gymfx_tpu.ops.window_zscore import fused_step_obs

    win, mean, std, neutral = _step_obs_case(b=4)
    cfg = _ObsCfg(binary_mask=(False,) * 3, feature_clip=10.0)
    ours = jax.vmap(
        lambda w_: fused_step_obs(
            w_, mean[0], std[0], neutral[0],
            binary_mask=cfg.binary_mask, clip=cfg.feature_clip,
            interpret=True,
        )
    )(win)
    ref = jax.vmap(
        lambda w_: scale_feature_window(w_, mean[0], std[0], neutral[0], cfg)
    )(win)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(ref))


# ---------------------------------------------------------------------------
# fused window attention (ops/fused_attention.py, VERDICT r4 weak #5)
# ---------------------------------------------------------------------------
def _qkv(shape, seed=0):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, np.float32) for k in ks)


# heads narrower than the 128 lanes go to the kernel PACKED on the minor
# axis (ops/fused_attention.py packed_lanes): H x D = 4 x 32 fills one lane
# group, 8 x 32 two (a grid axis over them), 2 x 32 half of one (the block
# spans the array); the decoder trunk's 20 x 256 stay apart, (B, H, S, D)
_F32, _BF16 = "float32", "bfloat16"
# what a bf16 kernel may differ by from the float32 reference on the same
# (rounded) inputs: outputs of order 1, gradients of sum(out ** 2) of order 5
_ATOL = {_F32: (2e-6, 2e-5), _BF16: (2e-2, 1e-1)}


def _qkv_as(shape, dtype, seed):
    import jax.numpy as jnp

    return tuple(x.astype(jnp.dtype(dtype)) for x in _qkv(shape, seed))


def _widened(xs):
    import jax.numpy as jnp

    return tuple(x.astype(jnp.float32) for x in xs)


@pytest.mark.parametrize("shape,causal,dtype,transform", [
    ((256, 4, 32), False, _F32, "direct"),
    ((64, 4, 32), True, _F32, "direct"),
    ((8, 128, 4, 32), False, _F32, "direct"),   # leading env-batch dim
    ((3, 64, 4, 32), True, _F32, "vmap"),       # the trainers' per-env vmap
    ((2, 64, 8, 32), False, _F32, "direct"),
    ((2, 64, 8, 32), True, _F32, "vmap"),
    ((2, 64, 2, 32), False, _F32, "vmap"),
    ((2, 64, 2, 32), True, _F32, "direct"),
    ((2, 64, 20, 256), True, _F32, "direct"),
    ((2, 64, 20, 256), True, _F32, "vmap"),
    ((4, 64, 4, 32), False, _BF16, "direct"),
    ((2, 64, 8, 32), True, _BF16, "vmap"),
    ((2, 64, 2, 32), False, _BF16, "direct"),
    ((2, 64, 20, 256), True, _BF16, "direct"),
])
def test_fused_attention_matches_reference(shape, causal, dtype, transform):
    import jax

    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    q, k, v = _qkv_as(shape, dtype, seed=0)

    def fused(q, k, v):
        return fused_window_attention(q, k, v, causal=causal, interpret=True)

    ours = (jax.vmap(fused) if transform == "vmap" else fused)(q, k, v)
    assert ours.dtype == q.dtype and ours.shape == q.shape
    ref = full_attention(*_widened((q, k, v)), causal=causal)
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), np.asarray(ref), atol=_ATOL[dtype][0])


@pytest.mark.parametrize("shape,causal,dtype,transform", [
    ((32, 2, 16), False, _F32, "grad"),
    ((64, 4, 32), True, _F32, "grad"),
    ((3, 64, 4, 32), False, _F32, "grad"),       # a batch: attend_batched
    ((3, 64, 4, 32), True, _F32, "grad_vmap"),   # the training update
    ((2, 64, 8, 32), False, _F32, "grad_vmap"),
    ((2, 64, 8, 32), True, _F32, "grad"),
    ((2, 64, 2, 32), True, _F32, "grad_vmap"),
    ((64, 2, 32), False, _F32, "grad"),
    ((2, 64, 20, 256), True, _F32, "grad_vmap"),
    ((64, 20, 256), True, _F32, "grad"),
    ((4, 64, 4, 32), False, _BF16, "grad_vmap"),
    ((2, 64, 8, 32), True, _BF16, "grad"),
    ((2, 64, 2, 32), False, _BF16, "grad_vmap"),
    ((2, 64, 20, 256), True, _BF16, "grad"),
])
def test_fused_attention_gradients_match_reference(shape, causal, dtype,
                                                   transform):
    """The custom VJP (pallas forward AND fused pallas backward, which
    recomputes the probabilities in VMEM) must produce the reference
    gradients — the kernel is on the TRAINING path of the transformer
    policies — in every transform order they reach it by."""
    import jax
    import jax.numpy as jnp

    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    q, k, v = _qkv_as(shape, dtype, seed=3)

    def fused(q, k, v):
        return fused_window_attention(q, k, v, causal=causal, interpret=True)

    if transform == "grad_vmap":
        fused = jax.vmap(fused)

    def loss_fused(q, k, v):
        return jnp.sum(fused(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(*_widened((q, k, v)))
    for a, b in zip(g_fused, g_ref):
        assert a.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=_ATOL[dtype][1])


@pytest.mark.parametrize("heads,head_dim", [(4, 32), (8, 32), (2, 32),
                                            (3, 48), (20, 256)])
def test_fused_packed_attention_is_the_same_attention(heads, head_dim):
    """q/k/v as a projection to d_model writes them, (..., W, H * D),
    give what their (..., W, H, D) views give — whether the kernel packs
    such heads on the lanes or takes them apart."""
    import jax
    import jax.numpy as jnp

    from gymfx_tpu.ops.fused_attention import (
        fused_packed_attention,
        fused_window_attention,
        packed_lanes,
    )

    assert packed_lanes(heads, head_dim) == {
        (4, 32): 128, (8, 32): 128, (2, 32): 64, (3, 48): 0, (20, 256): 0,
    }[heads, head_dim]
    q, k, v = _qkv((2, 32, heads * head_dim), seed=7)

    def packed(q, k, v):
        return fused_packed_attention(q, k, v, n_heads=heads, interpret=True)

    def parted(q, k, v):
        return fused_window_attention(
            *(x.reshape(2, 32, heads, head_dim) for x in (q, k, v)),
            interpret=True).reshape(q.shape)

    def grads(attend):
        return jax.grad(lambda *a: jnp.sum(attend(*a) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    for a, b in zip((packed(q, k, v), *grads(packed)),
                    (parted(q, k, v), *grads(parted))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.tpu
def test_fused_attention_gradients_exact_on_tpu():
    """Grad exactness of the COMPILED fused backward on a real chip
    (interpret-mode coverage above can't catch Mosaic lowering bugs).
    Skipped automatically off-TPU."""
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU (compiled pallas backward)")
    import jax.numpy as jnp

    from gymfx_tpu.ops.fused_attention import fused_window_attention
    from gymfx_tpu.parallel.ring_attention import full_attention

    q, k, v = _qkv((256, 4, 32), seed=5)

    def loss_fused(q, k, v):
        return jnp.sum(
            fused_window_attention(q, k, v, interpret=False) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v) ** 2)

    g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_fused_attention_refuses_oversized_windows():
    from gymfx_tpu.ops.fused_attention import fused_window_attention

    q, k, v = _qkv((2048, 1, 8))
    with pytest.raises(ValueError, match="ring/Ulysses"):
        fused_window_attention(q, k, v, interpret=True)


def test_dense_window_attention_dispatch_off_tpu_is_reference():
    """On non-TPU backends the policies' dense attention is the XLA
    twin exactly (the pallas path is TPU-only + interpret tests)."""
    from gymfx_tpu.parallel.ring_attention import full_attention
    from gymfx_tpu.train.policies import dense_window_attention

    q, k, v = _qkv((16, 2, 8))
    np.testing.assert_array_equal(
        np.asarray(dense_window_attention(q, k, v)),
        np.asarray(full_attention(q, k, v)),
    )


# ---------------------------------------------------------------------------
# ops/dispatch.py: the one platform decision behind the kernel switches
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode, tpu, expected", [
    ("off", False, None), ("off", True, None),
    ("interpret", False, True), ("interpret", True, True),
    # "on": the compiled kernel on a TPU, the plain-XLA twin on a CPU —
    # never the interpreter, and on a TPU never the twin
    ("on", True, False), ("on", False, None),
])
def test_kernel_switch_resolves_in_one_place(monkeypatch, mode, tpu, expected):
    from gymfx_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: tpu)
    assert dispatch.kernel_interpret(mode) is expected


def test_kernel_switch_rejects_unknown_mode_and_entry_default(monkeypatch):
    from gymfx_tpu.ops import dispatch

    with pytest.raises(ValueError, match="off"):
        dispatch.kernel_interpret("sideways")
    # interpret=None at a kernel entry: compiled on a TPU, interpreter
    # elsewhere; an explicit choice always wins
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert dispatch.resolve_interpret(None) is False
    assert dispatch.resolve_interpret(True) is True
    monkeypatch.setattr(dispatch, "on_tpu", lambda: False)
    assert dispatch.resolve_interpret(None) is True
    assert dispatch.resolve_interpret(False) is False


def test_no_platform_test_outside_the_dispatch_helper():
    """`jax.default_backend()` is asked in ops/dispatch.py and nowhere
    else in the package: eleven copies of the decision were how "on"
    came to mean three different things."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1] / "gymfx_tpu"
    hits = [
        str(p.relative_to(root)) for p in root.rglob("*.py")
        if "default_backend()" in p.read_text(encoding="utf-8")
    ]
    assert hits == ["ops/dispatch.py"], hits
