"""Analytic MFU and device-memory accounting.

bench_util.py derives MFU from the XLA cost model of the compiled
executable — exact, but only available where the backend exposes
``cost_analysis`` and only for programs we compiled ourselves.  This
module is the independent cross-check ISSUE'd for the telemetry PR: a
closed-form per-train-step FLOP model from the policy's parameter
shapes, so dashboards can sanity-check the cost-model number (and
report SOMETHING on backends that hide cost analysis).

Model (dense-matmul accounting, the standard MFU convention):

  * every 2-D parameter ``(m, n)`` is a GEMM costing ``2·m·n`` FLOPs
    per sample (per token for token policies) — biases/norms are
    rounding errors against the GEMMs and are ignored;
  * self-attention adds ``4·W²·d_model`` per layer per sample
    (``QKᵀ`` and ``A·V``, ``2·W²·d`` each) for window length ``W``
    (``causal``: a token meets the ``(W + 1) / 2`` keys up to itself);
  * with ``expert_share`` (an expert layer: experts per token ÷ routed
    experts) parameters of more than two dims are stacks of GEMMs (layers
    under a scan, the experts a chip holds) and a stack of experts
    (``experts_*``) costs its ACTIVE share: per layer experts held ×
    ``expert_share`` × ``2·m·n``, not every expert held for every token;
  * one train step = rollout forwards over ``num_envs · horizon``
    samples + update passes at the standard ``3×`` forward cost
    (forward + backward) over the same samples, ``update_epochs``
    times.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def param_flops_per_sample(params: Any, *, tokens: int = 1,
                           expert_share: Optional[float] = None) -> float:
    """``2·m·n`` summed over every 2-D leaf of ``params``, times the
    ``tokens`` each sample pushes through the trunk (1 for flat-obs
    policies, the window length for token policies).  With
    ``expert_share`` also the stacked leaves: ``2·m·n`` of the last two
    dims times the stack, an ``experts_*`` stack times ``expert_share``."""
    import math

    import jax

    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        shape = getattr(leaf, "shape", ())
        if len(shape) == 2:
            total += 2.0 * float(shape[0]) * float(shape[1])
        elif len(shape) > 2 and expert_share is not None:
            name = str(getattr(path[-1], "key", path[-1]))
            share = float(expert_share) if name.startswith("experts_") else 1.0
            total += 2.0 * share * float(math.prod(shape))
    return total * float(tokens)


def attention_flops_per_sample(window: int, d_model: int,
                               n_layers: int, causal: bool = False) -> float:
    """The activation-activation matmuls parameter counting misses:
    ``QKᵀ`` + ``A·V`` = ``4·W²·d`` per layer (``d``: heads × head width);
    causal, ``4·W·(W + 1)/2·d``."""
    keys = (float(window) + 1.0) / 2.0 if causal else float(window)
    return 4.0 * float(n_layers) * float(window) * keys * float(d_model)


def delta_rule_flops_per_sample(window: int, heads: int, head_dim: int,
                                n_layers: int, chunk: int = 64) -> float:
    """A linear-attention layer's gated delta rule over a window, as the
    chunked triangular solve any implementation has to do (key and value
    width ``head_dim``): per position and head, the two rows of intra-chunk
    scores (``chunk`` columns in all over the key width), the forward
    substitution and the scores' product with the pseudo-values (the same over
    the value width), and three key x value products with the carried state."""
    per_position = chunk * 2.0 * head_dim + 3.0 * head_dim * head_dim
    return 2.0 * float(n_layers) * float(window) * float(heads) * per_position


def analytic_train_step_flops(
    params: Any,
    *,
    num_envs: int,
    horizon: int,
    update_epochs: int = 1,
    tokens: int = 1,
    window: int = 0,
    d_model: int = 0,
    n_layers: int = 0,
    causal: bool = False,
    expert_share: Optional[float] = None,
    linear_layers: int = 0,
    linear_heads: int = 0,
    linear_head_dim: int = 0,
) -> float:
    """Closed-form FLOPs of ONE fused rollout+update train step.
    ``n_layers`` counts the layers with softmax attention, ``linear_layers``
    those with linear attention (``delta_rule_flops_per_sample``)."""
    fwd = param_flops_per_sample(params, tokens=tokens, expert_share=expert_share)
    if n_layers and window and d_model:
        fwd += attention_flops_per_sample(window, d_model, n_layers, causal)
    if linear_layers and window:
        fwd += delta_rule_flops_per_sample(window, linear_heads, linear_head_dim, linear_layers)
    samples = float(num_envs) * float(horizon)
    rollout = samples * fwd
    update = 3.0 * samples * fwd * float(max(1, update_epochs))
    return rollout + update


# ---------------------------------------------------------------------------
def hw_flops_peak(device: Any = None) -> Optional[float]:
    """Public peak dense-bf16 FLOPs/sec of ``device`` (default: the
    first local device); None when unknown (CPU)."""
    from gymfx_tpu.bench_util import device_peak_flops

    if device is None:
        import jax

        device = jax.local_devices()[0]
    return device_peak_flops(device)


def device_memory_bytes(device: Any = None) -> Optional[int]:
    """``bytes_in_use`` from the device allocator, or None where the
    backend does not expose memory stats (CPU)."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    raw = stats.get("bytes_in_use", stats.get("pool_bytes"))
    return None if raw is None else int(raw)


# the allocator stats worth a per-superstep gauge; peak_bytes_in_use is
# the watermark the OOM postmortems actually want
MEMORY_WATERMARK_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "largest_alloc_size",
)


def device_memory_watermarks(device: Any = None) -> Optional[dict]:
    """The allocator watermark slice of ``device.memory_stats()`` as
    ``{key: int}``, or None where the backend exposes no stats (CPU).
    A pure host-side allocator query — safe on the drain cadence, it
    never syncs the device."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {
        key: int(stats[key]) for key in MEMORY_WATERMARK_KEYS
        if stats.get(key) is not None
    }
    return out or None


def mfu_report(
    flops_per_step: Optional[float],
    step_time_s: Optional[float],
    device: Any = None,
) -> Dict[str, Any]:
    """The bench.py JSON slice: analytic FLOPs, hardware peak, their
    ratio, and device memory — every key always present, null where the
    backend cannot say (the bench contract schema pins the key set, not
    TPU availability)."""
    peak = hw_flops_peak(device)
    util = None
    if flops_per_step and peak and step_time_s and step_time_s > 0:
        util = (flops_per_step / step_time_s) / peak
    return {
        "analytic_flops_per_step": (
            float(flops_per_step) if flops_per_step else None
        ),
        "hw_flops_peak": peak,
        "mfu_analytic": round(util, 5) if util is not None else None,
        "device_memory_bytes": device_memory_bytes(device),
    }
