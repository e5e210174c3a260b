"""Ahead-of-time compiles for a described TPU v5e — no chip attached.

The chip's compiler is installed in the sandbox and compiles for a
topology that is DESCRIBED, not attached (on-chip-measurement guide
§2.3).  Interpret-mode parity tests cannot see what Mosaic refuses —
an unaligned block, a scatter, a select on mask vectors, more scoped
VMEM than a kernel may use — so each of the five Pallas kernels is
compiled here, with ``interpret=False`` steered in the test, at the
widths ``chip_smoke.py`` runs on the chip.  A compile that passes is
not a chip run: nothing executes, and no time or result comes of it.

All of it lives in this ONE file, behind a module-scoped fixture: only
one process may load libtpu, pytest-xdist hands a file to one worker,
and describing the topology at import (or in a ``skipif``) would give
the workers different collections.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gymfx_tpu.config import DEFAULT_VALUES
from gymfx_tpu.telemetry import scopes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the suite runs with x64 on; the chip path is f32 (broker.quantize
    # would otherwise put f64 arithmetic inside the env kernels)
    with jax.enable_x64(False):
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    """Lower + compile ``fn`` for the described chip; returns the HLO
    text so the caller can count ``tpu_custom_call``s."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes,
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _assert_kernels_named(hlo, name):
    """Every Mosaic custom call of the compiled text is named by the
    kernel's ``name`` (telemetry/scopes.py): ``%fused_attention_fwd.3`` in a
    step, where the call sits inside other scopes; called bare under a
    transform, as here, the transform wraps it
    (``%vmap_env_dynamics_fill_brackets_.1``)."""
    names = [line.split("=")[0].split()[-1] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert names and all(name in n for n in names), names


# ---------------------------------------------------------------------------
# fused window attention: (envs, window, heads, head_dim) of
# RingTransformerEncoder (train/policies.py) — forward and backward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("window, dtype", [
    (256, jnp.float32),      # the shape the score-only VMEM budget broke
    (256, jnp.bfloat16),
    (1024, jnp.float32),     # MAX_FUSED_WINDOW
])
def test_fused_attention_compiles(one_chip, window, dtype, direction):
    from gymfx_tpu.ops.fused_attention import (
        MAX_FUSED_WINDOW,
        fused_window_attention,
    )

    assert window <= MAX_FUSED_WINDOW
    x = _sds((256, window, 4, 32), dtype)

    def fwd(q, k, v):
        return fused_window_attention(q, k, v, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    hlo = _compile(fwd if direction == "fwd" else bwd, x, x, x,
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo
    # (the gradient of a sum needs no forward output: the backward alone)
    _assert_kernels_named(hlo, {"fwd": scopes.KERNEL_ATTENTION_FWD,
                                "bwd": scopes.KERNEL_ATTENTION_BWD}[direction])


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(64, 256, 20, 256), (16, 1024, 32, 64)],
                         ids=["latent_20x256_w256", "grouped_query_32x64_w1024"])
def test_fused_attention_compiles_causal_at_the_decoder_trunks_heads(one_chip, shape, direction):
    """``mla_moe_decoder`` at published widths, causal, a minibatch's windows:
    latent attention's 20 heads of 256 (192 nope + 64 rope for q.k, 256 for v)
    at window 256, a head a program; grouped-query attention's 32 heads of 64
    at window 1,024, the PACKED route (two heads a lane group of 128) at the
    longest window the kernel takes."""
    from gymfx_tpu.ops.fused_attention import fused_window_attention

    x = _sds(shape, jnp.bfloat16)

    def fwd(q, k, v):
        return fused_window_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    hlo = _compile(fwd if direction == "fwd" else bwd, x, x, x, sharding=one_chip)
    _assert_kernels_named(hlo, {"fwd": scopes.KERNEL_ATTENTION_FWD,
                                "bwd": scopes.KERNEL_ATTENTION_BWD}[direction])


# ---------------------------------------------------------------------------
# grouped products of an expert layer (ops/grouped_matmul.py) at the
# benchmark cell's shapes: 8 experts of 2048 x 3072 (gate|up) and 1536 x 2048
# (down), the short and the worst-case buffer of a 16,384-token minibatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("rows, k, n", [(20480, 2048, 3072), (69632, 1536, 2048)])
def test_grouped_matmul_compiles(one_chip, rows, k, n, direction):
    from gymfx_tpu.ops.grouped_matmul import grouped_matmul

    def fwd(lhs, rhs, group, used):
        return grouped_matmul(lhs, rhs, group, used, tile_rows=512, interpret=False)

    def bwd(lhs, rhs, group, used):
        return jax.grad(lambda lhs, rhs: fwd(lhs, rhs, group, used).astype(
            jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)

    hlo = _compile(
        fwd if direction == "fwd" else bwd,
        _sds((rows, k), jnp.bfloat16), _sds((8, k, n), jnp.bfloat16),
        _sds((rows // 512,), jnp.int32), _sds((1,), jnp.int32), sharding=one_chip)
    _assert_kernels_named(hlo, scopes.KERNEL_GROUPED_MATMUL)
    assert (scopes.KERNEL_GROUPED_MATMUL_DW in hlo) == (direction == "bwd")


# ---------------------------------------------------------------------------
# per-step obs kernel, the trainers' per-env vmap folded into the grid
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("features", [5, 8])
def test_fused_step_obs_compiles(one_chip, features):
    from gymfx_tpu.ops.window_zscore import fused_step_obs

    def obs(win, mean, std, neutral):
        return jax.vmap(
            lambda *a: fused_step_obs(
                *a, binary_mask=(False,) * features, clip=10.0,
                interpret=False,
            )
        )(win, mean, std, neutral)

    hlo = _compile(
        obs,
        _sds((8192, 32, features), jnp.float32),
        _sds((8192, features), jnp.float32),
        _sds((8192, features), jnp.float32),
        _sds((8192,), jnp.bool_),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# env dynamics: kernel A (fill + brackets) and kernel B (mark + reward)
# at the flagship's 8192 envs, on the broker chain's config axes
# ---------------------------------------------------------------------------
def _env_cfg(**over):
    from gymfx_tpu.core.types import make_env_config, make_env_params

    config = dict(DEFAULT_VALUES)
    config.update(window_size=32, timeframe="M1", rollout_env_kernel="on")
    config.update(over)
    cfg = make_env_config(config, n_bars=500)
    return cfg, make_env_params(config, cfg)


def _batched_state(cfg, n):
    from gymfx_tpu.core.types import initial_state

    one = jax.eval_shape(lambda: initial_state(cfg))
    return jax.tree.map(lambda s: _sds((n, *s.shape), s.dtype), one)


_BROKER_AXES = [
    {},
    {"strategy_plugin": "direct_fixed_sltp", "slip_limit": True,
     "slip_match": True, "slippage": 2e-4, "venue_quantization": True,
     "instrument": "EUR_USD", "intrabar_collision_policy": "ohlc",
     "limit_fill_policy": "conservative"},
]


@pytest.mark.parametrize("over", _BROKER_AXES, ids=["default", "all_axes"])
def test_env_fill_brackets_kernel_compiles(one_chip, over):
    from gymfx_tpu.ops.env_dynamics import fused_fill_brackets

    cfg, params = _env_cfg(**over)
    n = 8192
    bar = _sds((n,), jnp.float32)

    def kernel_a(st, o, h, l, c, advance):
        return jax.vmap(
            lambda st, o, h, l, c, adv: fused_fill_brackets(
                st, o, h, l, c, None, adv, cfg, params, interpret=False
            )
        )(st, o, h, l, c, advance)

    hlo = _compile(
        kernel_a, _batched_state(cfg, n), bar, bar, bar, bar,
        _sds((n,), jnp.bool_), sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo
    _assert_kernels_named(hlo, scopes.KERNEL_FILL_BRACKETS)


@pytest.mark.parametrize(
    "reward", ["pnl_reward", "dd_penalized_reward"]
)
def test_env_mark_reward_kernel_compiles(one_chip, reward):
    from gymfx_tpu.ops.env_dynamics import fused_mark_reward

    cfg, params = _env_cfg(reward_plugin=reward)
    n = 8192
    flag = _sds((n,), jnp.bool_)

    def kernel_b(st, c, mark, live):
        return jax.vmap(
            lambda st, c, m, lv: fused_mark_reward(
                st, c, m, lv, cfg, params, interpret=False
            )
        )(st, c, mark, live)

    hlo = _compile(
        kernel_b, _batched_state(cfg, n), _sds((n,), jnp.float32),
        flag, flag, sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo
    _assert_kernels_named(hlo, scopes.KERNEL_MARK_REWARD)


# ---------------------------------------------------------------------------
# the flagship's whole step (chip_smoke.flagship_config, fewer envs): what
# the chip's compiler leaves of the lookups by index.  A TPU gather costs
# per index (PERF.md section 6), so the step has them only where rows are
# really fetched: the packed tape row and the minibatch's fields.  The
# action's log-probability is picked by a select (train/common.picked_logp).
# ---------------------------------------------------------------------------
def test_flagship_step_gathers_only_the_tape_and_the_minibatch(one_chip, monkeypatch):
    from gymfx_tpu.ops import dispatch
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from
    from tests.helpers import gather_op_paths, make_env, uptrend_df

    # code that asks the backend sees the CPU here: steered in the test
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    env = make_env(
        uptrend_df(500), num_envs=1024, ppo_horizon=8, ppo_epochs=1,
        ppo_minibatches=4, policy="mlp", policy_dtype="bfloat16", window_size=32,
        ppo_minibatch_scheme="env_permute", rollout_collect_dtype="bfloat16",
        rollout_env_kernel="on")
    trainer = PPOTrainer(env, ppo_config_from(env.config))
    hlo = _compile(trainer._train_step_impl,
                   jax.eval_shape(trainer.init_state, 0), sharding=one_chip)

    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    paths = gather_op_paths(hlo)
    taken = [path for path in paths if f"/{scopes.MINIBATCH_TAKE}/" in path]
    read = [path for path in paths if f"/{scopes.TAPE_READ}/" in path]
    assert (len(paths), len(taken), len(read)) == (6, 5, 1), paths


# ---------------------------------------------------------------------------
# the transformer cell's whole step (benchmarks/configs/
# ppo_transformer_ring_w256_bf16.json, fewer envs): the layout of q, k, v, o
# and their gradients between the projections and the kernel.  The chip
# tiles a tensor's minor axis to 128 lanes, so a (B, 4, 256, 32) face is
# three quarters padding in HBM; the projections write (B, 256, 128) and the
# kernel reads that as it is (ops/fused_attention.py packed_lanes, PR 32).
# ---------------------------------------------------------------------------
# `  ROOT %name = <type, a tuple's has spaces> opcode(...`
_HLO_RESULT_RE = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) [\w\-]+\(")
_HLO_SHAPE_RE = re.compile(r"\w+\[([\d,]*)\]")


def _dims(shape_text):
    """Every array's dimensions in an HLO result type (a tuple has several)."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in _HLO_SHAPE_RE.findall(shape_text)]


def test_transformer_step_hands_the_kernel_lane_dense_qkv(one_chip, monkeypatch):
    from gymfx_tpu.ops import dispatch
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from
    from tests.helpers import make_env, uptrend_df

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    envs, horizon, window, d_model = 16, 8, 256, 128
    env = make_env(
        uptrend_df(500), num_envs=envs, ppo_horizon=horizon, ppo_epochs=1,
        ppo_minibatches=4, policy="transformer_ring", policy_dtype="bfloat16",
        window_size=window, ppo_minibatch_scheme="env_permute",
        rollout_collect_dtype="bfloat16")
    trainer = PPOTrainer(env, ppo_config_from(env.config))
    hlo = _compile(trainer._train_step_impl,
                   jax.eval_shape(trainer.init_state, 0), sharding=one_chip)
    lines = hlo.splitlines()
    result = {m.group(1): m.group(2)
              for m in map(_HLO_RESULT_RE.match, lines) if m}

    # 2 layers x (rollout forward, bootstrap forward, loss forward, backward)
    calls = [line for line in lines
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 8
    for line in calls:
        operands = re.findall(
            r"%([\w.\-]+)", line.split(" custom-call(")[1].split(")")[0])
        faces = _dims(_HLO_RESULT_RE.match(line).group(2)) + [
            dims for name in operands for dims in _dims(result[name])]
        assert len(faces) in (4, 7)     # q k v -> o; q k v g -> dq dk dv
        assert all(dims[1:] == (window, d_model) for dims in faces), faces

    # no (B, H, W, D) or (B, W, H, D) tensor anywhere in the step
    padded = [m.group(1) for m in map(_HLO_RESULT_RE.match, lines)
              if m and re.search(rf"\[\d+,(4,{window}|{window},4),32\]", m.group(2))]
    assert not padded, padded[:8]
    # and no window-sized tensor re-laid out between a projection and a call
    moved = [_HLO_RESULT_RE.match(line).group(1) for line in lines
             if re.search(r" (copy|transpose)\(", line)
             and f"/{scopes.ATTENTION}/" in line.split('op_name="')[-1]
             and any(math.prod(dims) >= envs * window * d_model
                     for dims in _dims(_HLO_RESULT_RE.match(line).group(2)))]
    assert not moved, moved[:8]


# ---------------------------------------------------------------------------
# the hybrid decoder cell's whole step (benchmarks/configs/
# ppo_ling3flash_ep64_bf16.json) at a cut size: the published head widths (KDA
# 128, MLA 128 | 64 keys against 128 values), window 1,024 and chunk 64, the
# six-layer pattern whole, fewer heads and narrower matrices.  The chunked scan
# is plain XLA (no custom call of its own); the MLA layer's values go to the
# attention kernel padded to the keys' width; the expert layers' products are
# the grouped kernels, one set a buffer length and pass.
# ---------------------------------------------------------------------------
def test_hybrid_decoder_step_compiles_with_its_kernels_by_name(one_chip, monkeypatch):
    from collections import Counter

    from gymfx_tpu.ops import dispatch
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from
    from tests.helpers import make_env, uptrend_df

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    env = make_env(
        uptrend_df(1500), num_envs=4, ppo_horizon=2, ppo_epochs=1, ppo_minibatches=2,
        policy="mla_moe_decoder", policy_dtype="bfloat16", window_size=1024,
        ppo_minibatch_scheme="sample_permute", rollout_collect_dtype="bfloat16",
        policy_kwargs=dict(
            hidden_size=512, q_lora_rank=None, kv_lora_rank=128, num_attention_heads=4,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, intermediate_size=1024,
            moe_intermediate_size=256, n_routed_experts=64, num_experts_per_tok=8, n_group=8,
            topk_group=4, routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=6e6,
            first_k_dense_replace=1, n_layers=6, experts_held=8, layer_group_size=6,
            attn_output_gate=True, kda_head_dim=128, kda_chunk=64))
    trainer = PPOTrainer(env, ppo_config_from(env.config))
    hlo = _compile(trainer._train_step_impl,
                   jax.eval_shape(trainer.init_state, 0), sharding=one_chip)
    names = [line.split("=")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
             for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    counts = Counter(names)
    assert set(counts) <= set(scopes.KERNEL_NAMES), counts
    # ONE latent-attention layer: rollout, bootstrap, the loss's forward and its
    # recomputation; one backward
    assert counts[scopes.KERNEL_ATTENTION_FWD] == 4 and counts[scopes.KERNEL_ATTENTION_BWD] == 1
    # two runs of expert layers (four KDA blocks under one scan, the MLA block)
    assert counts[scopes.KERNEL_GROUPED_MATMUL] == 40
    assert counts[scopes.KERNEL_GROUPED_MATMUL_DW] == 8
    # the attention kernel takes keys and values of ONE width: 192
    attention = [line for line in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line and "fused_attention" in line]
    assert all("1024,192]" in line for line in attention), attention[:1]
    # the scan's chunk products are there, batched over a window's 16 chunks x 4 heads
    assert re.search(r"\[16,4,64,64\]", hlo)


# ---------------------------------------------------------------------------
# the convolution-attention trunk's whole train step (benchmarks/configs/
# ppo_lfm2moe_ep8_bf16.json) at a cut size: the published head width (64, four
# query heads a key-value head), window 1,024, the five layers whole, fewer heads
# and narrower matrices.  The convolution layers are plain XLA; the ONE
# grouped-query layer hands the attention kernel q, k and v of one head count on
# its PACKED route (two 64-wide heads a lane group); no shared expert.
# ---------------------------------------------------------------------------
def test_conv_hybrid_decoder_step_compiles_with_its_kernels_by_name(one_chip, monkeypatch):
    from collections import Counter

    from gymfx_tpu.ops import dispatch
    from gymfx_tpu.train.ppo import PPOTrainer, ppo_config_from
    from tests.helpers import make_env, uptrend_df

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    env = make_env(
        uptrend_df(1500), num_envs=4, ppo_horizon=2, ppo_epochs=1, ppo_minibatches=2,
        policy="mla_moe_decoder", policy_dtype="bfloat16", window_size=1024,
        ppo_minibatch_scheme="env_permute", rollout_collect_dtype="bfloat16",
        policy_kwargs=dict(
            hidden_size=512, num_attention_heads=8, num_key_value_heads=2, conv_L_cache=3,
            intermediate_size=1024, moe_intermediate_size=256, n_routed_experts=64,
            num_experts_per_tok=4, n_shared_experts=0, routed_scaling_factor=1.0,
            first_k_dense_replace=1, n_layers=5, experts_held=8,
            layer_types=["conv", "conv", "conv", "conv", "full_attention"]))
    trainer = PPOTrainer(env, ppo_config_from(env.config))
    hlo = _compile(trainer._train_step_impl,
                   jax.eval_shape(trainer.init_state, 0), sharding=one_chip)
    calls = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    counts = Counter(line.split("=")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
                     for line in calls)
    assert set(counts) <= set(scopes.KERNEL_NAMES), counts
    # ONE grouped-query layer: rollout, bootstrap, the loss's forward and its
    # recomputation; one backward
    assert counts[scopes.KERNEL_ATTENTION_FWD] == 4 and counts[scopes.KERNEL_ATTENTION_BWD] == 1
    # two runs of expert layers (three convolution blocks under one scan, the attention block)
    assert counts[scopes.KERNEL_GROUPED_MATMUL] == 40
    assert counts[scopes.KERNEL_GROUPED_MATMUL_DW] == 8
    # q, k and v reach the kernel lane-dense with ONE head count: (windows, 1024, 8 x 64)
    attention = [line for line in calls if "fused_attention" in line]
    assert all("1024,512]" in line and "1024,128]" not in line for line in attention), (
        attention[:1])


# ---------------------------------------------------------------------------
# LOB stream matcher: 1024 books x 24 levels x 4 slots (venue default)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_msgs", [16, 256], ids=["seed16", "bench256"])
def test_lob_match_kernel_compiles(one_chip, n_msgs):
    from gymfx_tpu.lob.book import BookState, Messages
    from gymfx_tpu.ops.lob_match import fused_process_stream

    books, depth, slots = 1024, 24, 4
    lvl = _sds((books, depth), jnp.int32)
    slab = _sds((books, depth, slots), jnp.int32)
    book = BookState(lvl, slab, slab, lvl, slab, slab)
    msgs = Messages(*(_sds((books, n_msgs), jnp.int32) for _ in range(5)))

    def match(book, msgs):
        return jax.vmap(
            lambda b, m: fused_process_stream(b, m, interpret=False)
        )(book, msgs)

    hlo = _compile(match, book, msgs, sharding=one_chip)
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# q16 tape decode: one streamed shard (rows not lane-aligned on purpose)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cols, rows", [(5, 100_000), (9, 16_385)])
def test_tape_decode_kernel_compiles(one_chip, cols, rows):
    from gymfx_tpu.ops.tape_decode import decode_q16_block

    hlo = _compile(
        lambda d, b, i: decode_q16_block(d, b, i, interpret=False),
        _sds((cols, rows), jnp.int16), _sds((cols,), jnp.int32),
        _sds((cols,), jnp.float32), sharding=one_chip,
    )
    assert "tpu_custom_call" in hlo
