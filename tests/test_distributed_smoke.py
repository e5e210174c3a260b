"""Multi-PROCESS distributed smoke: two OS processes join one JAX
cluster through ``initialize_distributed`` (parallel/mesh.py), build a
shared 4-device mesh (2 local CPU devices each), and run one sharded
SGD step over a globally-sharded batch — the gradient all-reduce
crosses the process boundary (the DCN path of SURVEY.md §5.8).  Both
processes must agree with the single-process reference."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import json, os, sys
pid = int(sys.argv[1]); coord = sys.argv[2]
import jax

# the launcher exports JAX_PLATFORMS=cpu; pinned here too so the worker
# is a CPU process even when started by hand
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from gymfx_tpu.parallel.mesh import initialize_distributed, make_mesh

initialize_distributed(coord, 2, pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()

mesh = make_mesh({"data": 4})
xsh = NamedSharding(mesh, P("data"))

X = np.arange(16, dtype=np.float32).reshape(8, 2) / 16.0
Y = np.arange(8, dtype=np.float32) / 8.0
x = jax.make_array_from_callback((8, 2), xsh, lambda idx: X[idx])
y = jax.make_array_from_callback((8,), NamedSharding(mesh, P("data")),
                                 lambda idx: Y[idx])

@jax.jit
def sgd_step(w, x, y):
    def loss(w):
        return jnp.mean((x @ w - y) ** 2)
    return w - 0.1 * jax.grad(loss)(w)

w1 = sgd_step(jnp.zeros((2,)), x, y)  # grad all-reduce spans processes
print("RESULT " + json.dumps(np.asarray(jax.device_get(w1)).tolist()),
      flush=True)
"""


_TRAINER_WORKER = r"""
import json, sys
pid = int(sys.argv[1]); coord = sys.argv[2]; csv_path = sys.argv[3]
family = sys.argv[4]; csv2_path = sys.argv[5]
import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from gymfx_tpu.parallel.mesh import initialize_distributed, make_mesh

initialize_distributed(coord, 2, pid)
assert jax.process_count() == 2 and len(jax.devices()) == 4

from tests.helpers import build_smoke_trainer

trainer, state_cls, params_field = build_smoke_trainer(
    family, csv_path, csv2_path
)

mesh = make_mesh({"data": 4})
rep = NamedSharding(mesh, P())
batch = NamedSharding(mesh, P("data"))


def to_global(tree, sh):
    return jax.tree.map(
        lambda x: jax.make_array_from_callback(
            np.shape(x), sh, lambda idx: np.asarray(x)[idx]
        ),
        tree,
    )


# deterministic identical init on both processes, then globally placed:
# params/opt/rng (and every other scalar carry) replicated, the ENV
# BATCH sharded over all 4 devices — 2 per process, so the rollout and
# the gradient all-reduce both cross the process boundary
BATCHED = {"env_states", "obs_vec", "policy_carry"}
s = trainer.init_state_from_key(jax.random.PRNGKey(0))
state = state_cls(**{
    f: to_global(getattr(s, f), batch if f in BATCHED else rep)
    for f in s._fields
})

state, metrics = trainer.train_step(state)


@jax.jit
def fingerprint(params):
    return sum(jnp.sum(jnp.abs(x.astype(jnp.float64))) for x in jax.tree.leaves(params))


out = {
    "loss": float(jax.device_get(metrics["loss"])),
    "mean_reward": float(jax.device_get(metrics["mean_reward"])),
    "fingerprint": float(jax.device_get(fingerprint(getattr(state, params_field)))),
}
print("RESULT " + json.dumps(out), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# some jaxlib builds cannot run multi-process computations on the CPU
# backend at all; probe once (with the cheap SGD workers) and skip the
# whole module on such hosts instead of paying a worker-pair spawn per
# test just to read the same XlaRuntimeError four times
_UNSUPPORTED = "Multiprocess computations aren't implemented"


@pytest.fixture(scope="module")
def sgd_probe(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("dist_probe")
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), coord],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            cwd=os.getcwd(), text=True,
        )
        for pid in (0, 1)
    ]
    outs, errs, timed_out = [], [], False
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                timed_out = True
                out, err = "", "worker timed out"
            outs.append(out)
            errs.append(err)
    finally:
        # a failed worker must not leave its peer blocked on the
        # coordination barrier holding the port
        for q in procs:
            if q.poll() is None:
                q.kill()
    rcs = [p.returncode for p in procs]
    return {
        "ok": not timed_out and all(rc == 0 for rc in rcs),
        "timed_out": timed_out,
        "unsupported": any(_UNSUPPORTED in e for e in errs),
        "outs": outs,
        "errs": errs,
    }


def _require_multiprocess_cpu(sgd_probe):
    if sgd_probe["unsupported"]:
        pytest.skip("this jaxlib cannot run multiprocess computations "
                    "on the CPU backend")


def test_two_process_distributed_sgd_step(sgd_probe):
    _require_multiprocess_cpu(sgd_probe)
    if sgd_probe["timed_out"]:
        pytest.fail("distributed worker timed out")
    assert sgd_probe["ok"], (
        "worker failed:\n" + "\n".join(e[-3000:] for e in sgd_probe["errs"])
    )
    outs = sgd_probe["outs"]

    results = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert lines, f"no RESULT line in worker output: {out[-500:]}"
        results.append(np.asarray(json.loads(lines[0][len("RESULT "):])))

    # both processes hold the same replicated update...
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)
    # ...equal to the single-process reference
    X = np.arange(16, dtype=np.float32).reshape(8, 2) / 16.0
    Y = np.arange(8, dtype=np.float32) / 8.0
    grad = 2.0 * X.T @ (X @ np.zeros(2) - Y) / 8.0
    np.testing.assert_allclose(results[0], -0.1 * grad, rtol=1e-5)


@pytest.mark.parametrize("family", ["ppo", "impala", "portfolio"])
def test_two_process_fused_train_step(family, tmp_path, sgd_probe):
    """VERDICT r4 item #4 (PPO) extended to every trainer family
    (VERDICT r4 item #10): one REAL fused ``train_step`` with the env
    batch sharded across 2 processes (2 CPU devices each).  The rollout
    scan, advantage pass and the gradient all-reduce all cross the
    process boundary; both processes must agree with each other exactly
    and with the single-process run up to reduction-order rounding."""
    _require_multiprocess_cpu(sgd_probe)
    import pandas as pd

    def write_csv(name, start):
        closes = start * (1.0 + 2e-4) ** np.arange(60)
        df = pd.DataFrame({
            "DATE_TIME": pd.date_range("2024-01-01", periods=60, freq="1min"),
            "OPEN": closes, "HIGH": closes + 1e-5, "LOW": closes - 1e-5,
            "CLOSE": closes, "VOLUME": np.zeros(60),
        })
        path = tmp_path / name
        df.to_csv(path, index=False)
        return path

    csv_path = write_csv("uptrend.csv", 1.1)
    csv2_path = write_csv("uptrend2.csv", 1.3)

    worker = tmp_path / "trainer_worker.py"
    worker.write_text(_TRAINER_WORKER)
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), coord, str(csv_path),
             family, str(csv2_path)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            cwd=os.getcwd(), text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                pytest.fail("fused-trainer distributed worker timed out")
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()

    results = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert lines, f"no RESULT line in worker output: {out[-500:]}"
        results.append(json.loads(lines[0][len("RESULT "):]))

    # the two processes ran ONE program: identical replicated outputs
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], rel=1e-6)
    assert results[0]["fingerprint"] == pytest.approx(
        results[1]["fingerprint"], rel=1e-6
    )

    # single-process reference in THIS process (same init key, same data)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tests.helpers import build_smoke_trainer

    tr, _state_cls, params_field = build_smoke_trainer(
        family, csv_path, csv2_path
    )
    s = tr.init_state_from_key(jax.random.PRNGKey(0))
    s, metrics = tr.train_step(s)
    ref_loss = float(metrics["loss"])

    import jax.numpy as jnp

    @jax.jit
    def fingerprint(params):  # same formula as the worker's
        return sum(
            jnp.sum(jnp.abs(x.astype(jnp.float64)))
            for x in jax.tree.leaves(params)
        )

    ref_fp = float(fingerprint(getattr(s, params_field)))
    # parity up to f32 reduction-order rounding across device layouts
    assert results[0]["loss"] == pytest.approx(ref_loss, rel=1e-3)
    assert results[0]["fingerprint"] == pytest.approx(ref_fp, rel=1e-4)
