"""Elastic degraded-mesh training units (docs/resilience.md, "Elastic
training"): the pieces tools/elastic_chaos.py exercises end-to-end,
each pinned in isolation —

  * the ``mesh=`` fault grammar (parse, reject, strip-fired rewrite);
  * device-loss classification (simulated DeviceLossError vs real XLA
    phrases vs everything-else-propagates);
  * survivor-shape re-planning with the honor-or-reject
    ``elastic_shrink_policy`` and the stream-preserving predicate;
  * the MeshSupervisor health probes (injectable probe, no thread);
  * the ``run_elastic`` auto-resume controller against a scripted
    ``train_once`` (retry accounting, config rewrites, per-attempt
    ledgers, bounded retries, non-device-loss propagation);
  * ``initialize_distributed`` bounded retry -> CoordinatorTimeoutError;
  * ``checkpoint_keep`` newest-N retention (sidecars included, protect
    honored);
  * the ``gymfx_mesh_devices{state}`` gauges;
  * the every-knob-unset bitwise guarantee (armed-but-no-faults
    controller == plain passthrough on real training).
"""
import json

import numpy as np
import pytest

from gymfx_tpu.parallel.elastic import (
    ElasticReplanError,
    MeshSupervisor,
    elastic_entry,
    is_device_loss,
    plan_survivor_shape,
    run_elastic,
    stream_preserving,
    survivor_devices,
)
from gymfx_tpu.resilience.faults import (
    DeviceLossError,
    parse_fault_profile,
    strip_fired_mesh_events,
)


# ---------------------------------------------------------------------------
# fault grammar: the ``mesh=`` clause
# ---------------------------------------------------------------------------
def test_mesh_fault_grammar_parses_and_sorts():
    profile = parse_fault_profile("mesh=kill:3@2+kill:1@5;preempt_at=9")
    assert profile["mesh"] == [
        {"action": "kill", "device": 3, "at": 2},
        {"action": "kill", "device": 1, "at": 5},
    ]
    assert profile["preempt_at"] == 9
    # comma separation is equivalent, events sort by ``at``
    profile = parse_fault_profile("mesh=kill:0@7,kill:2@1")
    assert [ev["at"] for ev in profile["mesh"]] == [1, 7]


@pytest.mark.parametrize(
    "bad",
    [
        "mesh=kill",              # no device/superstep
        "mesh=kill:3",            # missing @<superstep>
        "mesh=kill:x@2",          # non-int device
        "mesh=kill:3@-1",         # negative superstep
        "mesh=stall:1@2",         # unknown mesh action
    ],
)
def test_mesh_fault_grammar_rejects_malformed_tokens(bad):
    with pytest.raises(ValueError):
        parse_fault_profile(bad)


def test_strip_fired_mesh_events_removes_only_fired_mesh_clauses():
    spec = "mesh=kill:3@2+kill:1@5;preempt_at=9;seed=7"
    # at=2 fired -> only the @5 event survives; other clauses verbatim
    out = strip_fired_mesh_events(spec, 2)
    assert parse_fault_profile(out)["mesh"] == [
        {"action": "kill", "device": 1, "at": 5}
    ]
    assert "preempt_at=9" in out and "seed=7" in out
    # everything fired -> the mesh clause drops entirely
    out = strip_fired_mesh_events(spec, 5)
    assert parse_fault_profile(out)["mesh"] == []
    assert "mesh=" not in out
    # inert inputs pass through
    assert strip_fired_mesh_events(None, 3) is None
    assert strip_fired_mesh_events("", 3) == ""


# ---------------------------------------------------------------------------
# device-loss classification
# ---------------------------------------------------------------------------
def test_is_device_loss_classification():
    assert is_device_loss(DeviceLossError([3], at=2))
    # real XLA runtime phrasing (any marker substring, case-insensitive)
    assert is_device_loss(RuntimeError("DEVICE_UNAVAILABLE: chip reset"))
    assert is_device_loss(RuntimeError("Socket closed by peer"))
    assert is_device_loss(RuntimeError("slice health check failed"))
    # a real bug / divergence / OOM must propagate, never retry-mask
    assert not is_device_loss(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not is_device_loss(ValueError("device lost"))  # wrong type
    assert not is_device_loss(KeyboardInterrupt())


def test_device_loss_error_carries_resume_fields():
    exc = DeviceLossError([3, 1], at=2, checkpoint_step=256, step_offset=64)
    assert exc.lost == (3, 1)
    assert exc.at == 2 and exc.checkpoint_step == 256
    assert exc.step_offset == 64
    assert "checkpoint at step 256" in str(exc)
    bare = DeviceLossError([0])
    assert bare.checkpoint_step is None and bare.at is None
    assert "no checkpoint" in str(bare)


# ---------------------------------------------------------------------------
# survivor re-planning: honor-or-reject
# ---------------------------------------------------------------------------
def test_plan_survivor_shape_shrinks_data_axis():
    assert plan_survivor_shape({"data": 4}) == {"data": 3}
    assert plan_survivor_shape({"data": 8}, n_lost=2) == {"data": 6}
    # the model axis is structural: the loss comes out of data
    assert plan_survivor_shape({"data": 4, "model": 2}, n_lost=2) == {
        "data": 3, "model": 2,
    }


def test_plan_survivor_shape_repartition_honors_divisibility():
    # 16 envs do not divide over 3 shards -> repartition to 2
    assert plan_survivor_shape({"data": 4}, must_divide=(16,)) == {"data": 2}
    # multiple constraints: both num_envs and the PBT population
    assert plan_survivor_shape(
        {"data": 8}, n_lost=3, must_divide=(16, 8)
    ) == {"data": 4}
    # a dividing shrink stays put
    assert plan_survivor_shape(
        {"data": 4}, n_lost=2, must_divide=(16,)
    ) == {"data": 2}


def test_plan_survivor_shape_reject_policy_raises():
    with pytest.raises(ElasticReplanError, match="reject"):
        plan_survivor_shape({"data": 4}, must_divide=(16,), policy="reject")
    # reject only fires when the constraint actually breaks
    assert plan_survivor_shape(
        {"data": 4}, n_lost=2, must_divide=(16,), policy="reject"
    ) == {"data": 2}


def test_plan_survivor_shape_error_cases():
    with pytest.raises(ElasticReplanError, match="empty"):
        plan_survivor_shape({})
    with pytest.raises(ElasticReplanError, match="no 'data' axis"):
        plan_survivor_shape({"model": 4})
    # not enough survivors to carry the model axis
    with pytest.raises(ElasticReplanError, match="surviving"):
        plan_survivor_shape({"data": 2, "model": 2}, n_lost=3)
    with pytest.raises(ValueError, match="elastic_shrink_policy"):
        plan_survivor_shape({"data": 4}, policy="maybe")


def test_stream_preserving_is_pure_coarsening():
    assert stream_preserving({"data": 4}, {"data": 2})
    assert stream_preserving({"data": 8}, {"data": 2})
    assert stream_preserving({"data": 4}, {"data": 4})
    # 4 -> 3 re-shards mid-stream: env order regroups
    assert not stream_preserving({"data": 4}, {"data": 3})
    # a changed model axis is never stream-preserving
    assert not stream_preserving(
        {"data": 4, "model": 2}, {"data": 4, "model": 1}
    )
    assert not stream_preserving({"data": 4}, {"data": 2, "model": 1})
    assert not stream_preserving({"data": 4}, {"data": 0})


def test_survivor_devices_excludes_global_indices():
    pool = ["d0", "d1", "d2", "d3"]
    assert survivor_devices([3], pool) == ["d0", "d1", "d2"]
    assert survivor_devices([0, 2], pool) == ["d1", "d3"]
    assert survivor_devices([], pool) == pool


# ---------------------------------------------------------------------------
# MeshSupervisor: deterministic probes, no thread
# ---------------------------------------------------------------------------
def test_mesh_supervisor_probe_classification_and_dead_after():
    failing = {2}

    def probe(device):
        if device in failing:
            raise RuntimeError("DEVICE_UNAVAILABLE")
        return 1.0

    sup = MeshSupervisor(devices=[0, 1, 2, 3], dead_after=2, probe=probe)
    states = sup.poll_once()
    assert states == {0: "healthy", 1: "healthy", 2: "degraded", 3: "healthy"}
    # second consecutive failure crosses dead_after
    states = sup.poll_once()
    assert states[2] == "dead"
    assert sup.snapshot() == {"healthy": 3, "degraded": 0, "dead": 1}
    # recovery resets the failure count
    failing.clear()
    states = sup.poll_once()
    assert states[2] == "healthy"
    assert sup.polls == 3


def test_mesh_supervisor_mark_lost_is_immediate_and_counted():
    sup = MeshSupervisor(devices=[0, 1, 2, 3], probe=lambda d: 1.0)
    assert sup.degrades == 0
    sup.mark_lost([3])
    assert sup.classify()[3] == "dead"
    assert sup.snapshot() == {"healthy": 3, "degraded": 0, "dead": 1}
    assert sup.degrades == 1
    # re-marking the same device is not a new degrade event
    sup.mark_lost([3])
    assert sup.degrades == 1
    sup.mark_lost([1])
    assert sup.degrades == 2
    # a lost device stays dead through probes that would pass
    assert sup.poll_once()[3] == "dead"


def test_mesh_supervisor_gauges_read_live_state():
    from gymfx_tpu.telemetry.registry import (
        MetricsRegistry,
        register_mesh_health,
    )

    registry = MetricsRegistry()
    sup = MeshSupervisor(devices=[0, 1, 2, 3], probe=lambda d: 1.0)
    register_mesh_health(registry, sup, name="ppo")
    g = registry.gauge("gymfx_mesh_devices", labels=("state",))
    assert g.value(state="healthy") == 4.0
    assert g.value(state="dead") == 0.0
    sup.mark_lost([0, 2])
    # callback gauges: no re-registration needed, they read the LIVE
    # supervisor
    assert g.value(state="healthy") == 2.0
    assert g.value(state="dead") == 2.0
    g2 = registry.gauge("gymfx_mesh_degrades_total", labels=("name",))
    assert g2.value(name="ppo") == 1.0


# ---------------------------------------------------------------------------
# ResilientLoop: the ``mesh=`` event fires at the superstep boundary
# ---------------------------------------------------------------------------
class _Ledger:
    def __init__(self):
        self.rows = []

    def record(self, kind, **fields):
        self.rows.append({"kind": kind, **fields})


class _Recorder:
    def __init__(self):
        self.dumps = []

    def dump(self, reason, extra=None):
        self.dumps.append({"reason": reason, **(extra or {})})


def test_resilient_loop_mesh_fault_fires_with_forensics(tmp_path):
    from gymfx_tpu.resilience.loop import ResilientLoop

    ledger, recorder = _Ledger(), _Recorder()
    sup = MeshSupervisor(devices=[0, 1, 2, 3], probe=lambda d: 1.0)
    loop = ResilientLoop(
        steps_per_iter=128,
        checkpoint_dir=None,
        step_offset=0,
        max_consecutive_skips=0,
        mesh_faults=({"action": "kill", "device": 3, "at": 2},),
        supervisor=sup,
        ledger=ledger,
        recorder=recorder,
    )
    state_fn = lambda: ({}, None)  # noqa: E731 - never reached (no ckpt dir)
    loop.after_superstep(0, 1, {}, state_fn)  # it_end=1 < 2: no fire
    with pytest.raises(DeviceLossError) as ei:
        loop.after_superstep(1, 1, {}, state_fn)
    exc = ei.value
    assert exc.lost == (3,) and exc.at == 2
    assert exc.checkpoint_step is None  # nothing checkpointed yet
    # forensics fired in order: degrade row + postmortem + supervisor
    degrade = [r for r in ledger.rows if r["kind"] == "mesh_degrade"]
    assert degrade == [
        {"kind": "mesh_degrade", "lost": [3], "at": 2, "checkpoint_step": None}
    ]
    assert recorder.dumps == [{"reason": "device_loss", "lost": [3], "at": 2}]
    assert sup.classify()[3] == "dead" and sup.degrades == 1


def test_resilient_loop_mesh_fault_fires_on_fused_superstep_boundary():
    """A fused k>1 dispatch fires the event at the first boundary
    REACHING ``at`` — and the event never fires twice."""
    from gymfx_tpu.resilience.loop import ResilientLoop

    loop = ResilientLoop(
        steps_per_iter=8,
        max_consecutive_skips=0,
        mesh_faults=({"action": "kill", "device": 1, "at": 3},),
    )
    with pytest.raises(DeviceLossError) as ei:
        loop.after_superstep(0, 4, {}, lambda: ({}, None))
    assert ei.value.at == 4  # boundary, not the requested iteration
    # the fired event is consumed
    loop.after_superstep(4, 4, {}, lambda: ({}, None))


# ---------------------------------------------------------------------------
# run_elastic: the auto-resume controller against a scripted trainer
# ---------------------------------------------------------------------------
def _scripted_trainer(script):
    """A fake ``train_once``: pops the next script entry per call —
    an exception instance raises, anything else returns.  Records the
    config each call saw."""
    calls = []

    def train_once(cfg):
        calls.append(dict(cfg))
        action = script.pop(0)
        if isinstance(action, BaseException):
            raise action
        return dict(action)

    return train_once, calls


def test_run_elastic_resumes_once_with_rewritten_config():
    train_once, calls = _scripted_trainer([
        DeviceLossError([3], at=2, checkpoint_step=256, step_offset=0),
        {"final_step": 512},
    ])
    slept = []
    config = {
        "mesh_shape": {"data": 4},
        "train_total_steps": 512,
        "elastic_resume": True,
        "elastic_max_retries": 2,
        "elastic_backoff_s": 0.5,
        "fault_profile": "mesh=kill:3@2",
        "telemetry_ledger": "/runs/x/ledger.jsonl",
    }
    summary = run_elastic(
        train_once, config, must_divide=(16,), sleep=slept.append
    )
    assert len(calls) == 2
    retry = calls[1]
    # 16 envs over 3 survivors -> repartition to {"data": 2}
    assert retry["mesh_shape"] == {"data": 2}
    assert retry["elastic_exclude_devices"] == [3]
    assert retry["resume_training"] is True
    assert retry["elastic_attempt"] == 1
    # 512 requested, 256 safely checkpointed -> 256 remain
    assert retry["train_total_steps"] == 256
    # the fired mesh event is stripped so the retry cannot re-kill
    assert "mesh=" not in (retry["fault_profile"] or "")
    # per-attempt ledger keeps each file's seq monotonic
    assert retry["telemetry_ledger"] == "/runs/x/ledger.attempt1.jsonl"
    assert slept == [0.5]
    # the caller's dict is never mutated
    assert config["mesh_shape"] == {"data": 4}
    assert "elastic_exclude_devices" not in config
    # the summary carries the audit block
    el = summary["elastic"]
    assert el["attempts"] == 1
    assert el["mesh_shape"] == {"data": 2}
    assert el["lost_devices"] == [3]
    assert el["degrades"][0]["checkpoint_step"] == 256
    assert el["degrades"][0]["stream_preserving"] is True


def test_run_elastic_maps_local_indices_to_global_and_accumulates():
    """The second loss names device 0 of the SHRUNK mesh — the global
    exclusion list must not re-evict global device 0 twice."""
    train_once, calls = _scripted_trainer([
        DeviceLossError([0], at=1, checkpoint_step=128),
        DeviceLossError([0], at=2, checkpoint_step=256),
        {"final_step": 512},
    ])
    summary = run_elastic(
        train_once,
        {
            "mesh_shape": {"data": 4},
            "train_total_steps": 512,
            "elastic_resume": True,
            "elastic_max_retries": 2,
        },
        sleep=lambda s: None,
    )
    # global 0 died first; local 0 of the survivors {1,2,3} is global 1
    assert calls[2]["elastic_exclude_devices"] == [0, 1]
    assert calls[1]["mesh_shape"] == {"data": 3}
    assert calls[2]["mesh_shape"] == {"data": 2}
    assert summary["elastic"]["attempts"] == 2
    assert summary["elastic"]["lost_devices"] == [0, 1]
    # train_total_steps always counts from the ORIGINAL requested end
    assert calls[1]["train_total_steps"] == 384
    assert calls[2]["train_total_steps"] == 256


def test_run_elastic_bounded_retries_then_reraises():
    losses = [
        DeviceLossError([0], at=1, checkpoint_step=None) for _ in range(3)
    ]
    train_once, calls = _scripted_trainer(list(losses))
    with pytest.raises(DeviceLossError):
        run_elastic(
            train_once,
            {
                "mesh_shape": {"data": 8},
                "train_total_steps": 64,
                "elastic_max_retries": 2,
            },
            sleep=lambda s: None,
        )
    assert len(calls) == 3  # initial + 2 retries, then give up


def test_run_elastic_propagates_non_device_loss():
    train_once, calls = _scripted_trainer([ValueError("a real bug")])
    with pytest.raises(ValueError, match="a real bug"):
        run_elastic(
            train_once,
            {"mesh_shape": {"data": 4}, "elastic_max_retries": 5},
        )
    assert len(calls) == 1  # never retried


def test_run_elastic_without_mesh_shape_raises_replan_error():
    train_once, _ = _scripted_trainer([DeviceLossError([0], at=1)])
    with pytest.raises(ElasticReplanError, match="mesh_shape"):
        run_elastic(train_once, {"elastic_max_retries": 2})


def test_run_elastic_reject_policy_refuses_the_repartition():
    train_once, _ = _scripted_trainer([
        DeviceLossError([3], at=2, checkpoint_step=256)
    ])
    with pytest.raises(ElasticReplanError, match="reject"):
        run_elastic(
            train_once,
            {
                "mesh_shape": {"data": 4},
                "elastic_max_retries": 2,
                "elastic_shrink_policy": "reject",
            },
            must_divide=(16,),
        )


def test_run_elastic_clean_run_has_no_elastic_block():
    train_once, calls = _scripted_trainer([{"final_step": 64}])
    summary = run_elastic(
        train_once, {"mesh_shape": {"data": 4}, "elastic_resume": True}
    )
    assert "elastic" not in summary
    assert len(calls) == 1


def test_elastic_entry_is_passthrough_when_unset():
    """The bitwise-unset gate: without ``elastic_resume`` the entry IS
    ``train_once(config)`` — same object in, no copy, no wrapper."""
    seen = []

    def train_once(cfg):
        seen.append(cfg)
        return {"ok": True}

    config = {"mesh_shape": {"data": 4}}
    out = elastic_entry(train_once, config)
    assert out == {"ok": True}
    assert seen[0] is config  # the very same dict — not even copied


# ---------------------------------------------------------------------------
# initialize_distributed: bounded retry, typed timeout
# ---------------------------------------------------------------------------
def test_initialize_distributed_noop_without_coordinator():
    from gymfx_tpu.parallel.mesh import initialize_distributed

    called = []
    initialize_distributed(_initialize=lambda **kw: called.append(kw))
    assert called == []


def test_initialize_distributed_retries_then_succeeds():
    from gymfx_tpu.parallel.mesh import initialize_distributed

    attempts, slept = [], []

    def init(**kwargs):
        attempts.append(kwargs)
        if len(attempts) < 3:
            raise RuntimeError("failed to connect to coordinator")

    initialize_distributed(
        "host:1234", 4, 1, retries=3, backoff_s=1.0,
        _initialize=init, _sleep=slept.append,
    )
    assert len(attempts) == 3
    assert attempts[0]["coordinator_address"] == "host:1234"
    assert attempts[0]["num_processes"] == 4
    assert attempts[0]["process_id"] == 1
    assert slept == [1.0, 2.0]  # linear backoff between attempts


def test_initialize_distributed_exhausts_into_typed_error():
    from gymfx_tpu.parallel.mesh import (
        CoordinatorTimeoutError,
        initialize_distributed,
    )

    def init(**kwargs):
        raise ConnectionError("socket closed")

    with pytest.raises(CoordinatorTimeoutError) as ei:
        initialize_distributed(
            "host:1234", retries=2, backoff_s=0.0,
            _initialize=init, _sleep=lambda s: None,
        )
    exc = ei.value
    assert isinstance(exc, TimeoutError)  # launchers can catch broadly
    assert exc.coordinator_address == "host:1234"
    assert exc.attempts == 2
    assert isinstance(exc.cause, ConnectionError)


def test_initialize_distributed_passes_timeout_kwarg_through():
    """``timeout_s`` reaches ``jax.distributed.initialize`` as
    ``initialization_timeout`` (the installed JAX takes it); a
    ``TypeError`` from the call is a bug and is raised as it is, not
    retried without the kwarg."""
    from gymfx_tpu.parallel.mesh import initialize_distributed

    attempts = []
    initialize_distributed(
        "host:1234", retries=1, timeout_s=30.9,
        _initialize=lambda **kw: attempts.append(kw),
        _sleep=lambda s: None,
    )
    assert [a["initialization_timeout"] for a in attempts] == [30]

    def init(**kwargs):
        raise TypeError("unexpected keyword argument")

    with pytest.raises(TypeError):
        initialize_distributed(
            "host:1234", retries=3, timeout_s=30.0,
            _initialize=init, _sleep=lambda s: None,
        )


# ---------------------------------------------------------------------------
# checkpoint retention: newest-N, sidecars included, protect honored
# ---------------------------------------------------------------------------
def _fake_checkpoint_tree(root, steps, payload=b"x" * 64):
    """Step dirs + digest/empty-leaves sidecars, no orbax needed —
    prune_checkpoints works on the directory layout alone."""
    for step in steps:
        d = root / str(step)
        d.mkdir(parents=True)
        (d / "params.bin").write_bytes(payload)
        (root / f"digest_{step}.json").write_text(
            json.dumps({"digest": "d" * 8, "files": 1})
        )
        (root / f"empty_leaves_{step}.json").write_text("[]")


def test_prune_checkpoints_newest_n_with_sidecars(tmp_path):
    from gymfx_tpu.train.checkpoint import prune_checkpoints

    _fake_checkpoint_tree(tmp_path, [128, 256, 384, 512])
    pruned = prune_checkpoints(str(tmp_path), keep=2)
    assert [row["step"] for row in pruned] == [128, 256]
    assert all(row["bytes"] > 0 for row in pruned)
    # survivors intact, pruned steps gone SIDECARS INCLUDED (an
    # orphaned digest would read as corruption in the audit)
    assert sorted(
        int(p.name) for p in tmp_path.iterdir() if p.is_dir()
    ) == [384, 512]
    assert not (tmp_path / "digest_128.json").exists()
    assert not (tmp_path / "empty_leaves_256.json").exists()
    assert (tmp_path / "digest_384.json").exists()


def test_prune_checkpoints_protects_the_resume_step(tmp_path):
    from gymfx_tpu.train.checkpoint import prune_checkpoints

    _fake_checkpoint_tree(tmp_path, [128, 256, 384, 512])
    pruned = prune_checkpoints(str(tmp_path), keep=1, protect=(128,))
    # 128 is the active-resume entry: never pruned regardless of age
    assert [row["step"] for row in pruned] == [256, 384]
    assert (tmp_path / "128").is_dir() and (tmp_path / "512").is_dir()


def test_prune_checkpoints_keep_zero_is_a_noop(tmp_path):
    from gymfx_tpu.train.checkpoint import prune_checkpoints

    _fake_checkpoint_tree(tmp_path, [128, 256])
    assert prune_checkpoints(str(tmp_path), keep=0) == []
    assert prune_checkpoints(str(tmp_path), keep=-3) == []
    assert (tmp_path / "128").is_dir() and (tmp_path / "256").is_dir()


def test_prune_checkpoints_keep_larger_than_tree(tmp_path):
    from gymfx_tpu.train.checkpoint import prune_checkpoints

    _fake_checkpoint_tree(tmp_path, [128])
    assert prune_checkpoints(str(tmp_path), keep=5) == []
    assert (tmp_path / "128").is_dir()


def test_checkpoint_audit_reports_prunable_bytes(tmp_path, capsys):
    """tools/checkpoint_audit.py --keep N: flags prunable steps and the
    reclaimable bytes WITHOUT deleting anything."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "checkpoint_audit",
        Path(__file__).resolve().parent.parent / "tools" / "checkpoint_audit.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    _fake_checkpoint_tree(tmp_path, [128, 256, 384])
    # fake digests do not verify -> use --json to read rows, ignore rc 1
    rc = mod.main([str(tmp_path), "--json", "--keep", "2"])
    out = capsys.readouterr()
    rows = {r["step"]: r for r in json.loads(out.out)}
    assert rows[128]["prunable"] is True
    assert rows[256]["prunable"] is False and rows[384]["prunable"] is False
    assert all(r["bytes"] > 0 for r in rows.values())
    assert "1 prunable step(s)" in out.err
    # audit is read-only
    assert (tmp_path / "128").is_dir()
    assert rc in (0, 1)


# ---------------------------------------------------------------------------
# the bitwise-unset guarantee on REAL training
# ---------------------------------------------------------------------------
def test_elastic_knobs_unset_is_bitwise_identical(tmp_path):
    """Acceptance pin: every elastic knob unset -> byte-for-byte the
    pre-elastic path.  An ARMED controller with no faults must also be
    a plain passthrough: same final params, bit for bit."""
    from gymfx_tpu.config import DEFAULT_VALUES
    from gymfx_tpu.train.checkpoint import load_params
    from gymfx_tpu.train.ppo import train_from_config
    from tests.helpers import uptrend_df

    csv = tmp_path / "d.csv"
    uptrend_df(60).reset_index().to_csv(csv, index=False)

    def run(tag, **extra):
        ckpt = tmp_path / tag
        config = dict(DEFAULT_VALUES)
        config.update(
            input_data_file=str(csv), window_size=8, timeframe="M1",
            num_envs=4, ppo_horizon=8, ppo_epochs=1, ppo_minibatches=2,
            train_total_steps=64, policy_kwargs={"hidden": [16]},
            checkpoint_dir=str(ckpt), save_config=None, results_file=None,
            seed=11, quiet_mode=True,
        )
        config.update(extra)
        train_from_config(config)
        params, _ = load_params(str(ckpt))
        import jax

        return b"".join(
            np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(params)
        )

    baseline = run("baseline")
    armed = run(
        "armed", elastic_resume=True, elastic_max_retries=2,
        elastic_shrink_policy="repartition", checkpoint_keep=0,
    )
    assert baseline == armed
