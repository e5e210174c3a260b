"""MFU helpers (VERDICT r4 item #2): peak table lookup, XLA FLOP
counting, and the utilization arithmetic."""
import types

import numpy as np
import pytest

from gymfx_tpu.bench_util import (
    PEAK_BF16_FLOPS,
    compile_with_flops,
    device_peak_flops,
    mfu,
)


def _dev(kind):
    return types.SimpleNamespace(device_kind=kind, platform="tpu")


def test_peak_lookup_is_exact_and_unknown_kind_is_an_error():
    # keyed by the exact device_kind string the chip reports
    assert device_peak_flops(_dev("TPU v5 lite")) == 197e12
    # no substring matching: a near-miss or an unlisted generation is an
    # error on a measuring path, never a silent None or a default
    for kind in ("tpu v5litepod-8", "TPU v5p", "TPU v7x", None):
        with pytest.raises(KeyError, match="PEAK_BF16_FLOPS"):
            device_peak_flops(_dev(kind))
    # a CPU is a functional proxy: no peak, no utilization
    cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
    assert device_peak_flops(cpu) is None


def test_mfu_arithmetic():
    dev = _dev("TPU v5 lite")
    peak = PEAK_BF16_FLOPS["TPU v5 lite"]
    # 10 iters of 1e12 FLOPs in 1s -> 1e13 FLOPs/s
    assert mfu(1e12, 10, 1.0, dev) == pytest.approx(1e13 / peak)
    assert mfu(None, 10, 1.0, dev) is None
    cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
    assert mfu(1e12, 10, 1.0, cpu) is None
    assert mfu(1e12, 10, 0.0, dev) is None


def test_sweep_trainer_builders_honor_window():
    """The sweep's artifact rows record the job's window — every trainer
    builder must actually build the env at that window (r4 review
    finding: a silently-ignored window would publish a configuration
    that was never run)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools.tpu_bench import (
        _impala_trainer,
        _portfolio_trainer,
        _single_pair_trainer,
    )

    assert _single_pair_trainer("mlp", 8, 8, window=16).env.cfg.window_size == 16
    assert _impala_trainer(8, 8, window=16).env.cfg.window_size == 16
    assert _portfolio_trainer(8, 8, window=16).env.cfg.window_size == 16


def test_compiled_step_flops_counts_a_matmul():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.ones((64, 64), jnp.float32)
    compiled, flops = compile_with_flops(f, a, a)
    np.testing.assert_allclose(np.asarray(compiled(a, a)), 64.0)
    # cost analysis may have no count on some backends (None); when
    # present, a 64^3 matmul is ~2*64^3 = 524k flops
    if flops is not None:
        assert flops >= 2 * 64**3 * 0.5
    # a program that cannot be compiled raises — it never degrades to
    # (None, None) and a silent fall-through to jit dispatch
    with pytest.raises(AttributeError):
        compile_with_flops(object())


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch, tmp_path):
    """One rule (gymfx_tpu/compile_cache.py): JAX_COMPILATION_CACHE_DIR
    set -> left alone, no directory set in code; unset -> ONE fixed
    directory inside the checkout, never a temporary name."""
    import jax

    from gymfx_tpu import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        placed = str(tmp_path / "placed")
        monkeypatch.setenv(compile_cache.CACHE_ENV, placed)
        assert compile_cache.enable_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        fixed = compile_cache.enable_compile_cache()
        assert fixed == str(compile_cache.DEFAULT_CACHE_DIR)
        assert fixed == compile_cache.enable_compile_cache()   # stable
        assert jax.config.jax_compilation_cache_dir == fixed
        root = compile_cache.DEFAULT_CACHE_DIR.parent
        assert (root / "chip_smoke.py").exists()               # in checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_temporary_compile_cache_names_in_entry_points():
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    files = [root / "bench.py", root / "bench_infer.py",
             root / "chip_smoke.py", *root.glob("tools/*.py"),
             *root.glob("gymfx_tpu/**/*.py")]
    texts = {p: p.read_text(encoding="utf-8") for p in files}
    offenders = [
        str(p.relative_to(root)) for p, text in texts.items()
        if "mkdtemp" in text and "compilation_cache" in text
    ]
    assert offenders == [], offenders
