"""Launcher hostfile/filter parsing tests (model: reference tests/unit/test_run.py)
plus END-TO-END launches: runner.py -> launch.py -> user script, single-node and
a fake-pdsh two-"host" job whose processes rendezvous via jax.distributed and
run one engine step (reference launch flow: deepspeed/launcher/launch.py:65-129)."""

import json
import os
import stat
import subprocess
import sys

import pytest

from tests.unit.simple_model import free_port

from deepspeed_tpu.launcher.runner import (
    decode_world_info,
    encode_world_info,
    fetch_hostfile,
    parse_resource_filter,
)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text(
        """
worker-0 slots=4
worker-1 slots=4
# comment line
worker-2 slots=2
""".strip()
    )
    return str(p)


def test_fetch_hostfile(hostfile):
    pool = fetch_hostfile(hostfile)
    assert list(pool.keys()) == ["worker-0", "worker-1", "worker-2"]
    assert pool["worker-0"] == 4
    assert pool["worker-2"] == 2


def test_fetch_hostfile_missing():
    assert fetch_hostfile("/nonexistent/hostfile") is None


def test_fetch_hostfile_bad_format(tmp_path):
    p = tmp_path / "bad"
    p.write_text("worker-0 gpus=4\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(p))


def test_fetch_hostfile_duplicate(tmp_path):
    p = tmp_path / "dup"
    p.write_text("worker-0 slots=4\nworker-0 slots=2\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(p))


def _pool():
    return {"worker-0": 4, "worker-1": 4}


def test_no_filter():
    out = parse_resource_filter(_pool())
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3]}


def test_include_whole_host():
    out = parse_resource_filter(_pool(), include_str="worker-1")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_include_slots():
    out = parse_resource_filter(_pool(), include_str="worker-0:0,2")
    assert out == {"worker-0": [0, 2]}


def test_include_multi_host():
    out = parse_resource_filter(_pool(), include_str="worker-0:1@worker-1:3")
    assert out == {"worker-0": [1], "worker-1": [3]}


def test_exclude_whole_host():
    out = parse_resource_filter(_pool(), exclude_str="worker-0")
    assert out == {"worker-1": [0, 1, 2, 3]}


def test_exclude_slots():
    out = parse_resource_filter(_pool(), exclude_str="worker-1:1,2")
    assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 3]}


def test_include_and_exclude_conflict():
    with pytest.raises(ValueError):
        parse_resource_filter(_pool(), include_str="worker-0", exclude_str="worker-1")


def test_include_unknown_host():
    with pytest.raises(ValueError):
        parse_resource_filter(_pool(), include_str="worker-9")


def test_include_unknown_slot():
    with pytest.raises(ValueError):
        parse_resource_filter(_pool(), include_str="worker-0:7")


def test_world_info_roundtrip():
    info = {"worker-0": [0, 1], "worker-1": [0]}
    assert decode_world_info(encode_world_info(info)) == info


# ---------------------------------------------------------------------------
# end-to-end launches
# ---------------------------------------------------------------------------

# Training payload: rendezvous (env contract set by launch.py), one engine
# step on the global mesh, write a per-rank sentinel with the loss.
TRAIN_SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.environ["DSTPU_REPO"])
import deepspeed_tpu
deepspeed_tpu.init_distributed(verbose=False)
import jax, jax.numpy as jnp, numpy as np
import flax.linen as nn

class M(nn.Module):
    @nn.compact
    def __call__(self, x, y):
        return jnp.mean((nn.Dense(8)(x) - y) ** 2)

n = jax.device_count()  # GLOBAL device count after rendezvous
model = M()
x0 = jnp.ones((n, 8), jnp.float32)
params = model.init(jax.random.PRNGKey(0), x0, jnp.zeros((n, 8), jnp.float32))
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
    config_params={"train_batch_size": n, "train_micro_batch_size_per_gpu": 1,
                   "gradient_accumulation_steps": 1,
                   "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
rng = np.random.RandomState(0)
x = rng.randn(n, 8).astype(np.float32)   # same global batch on every host
y = rng.randn(n, 8).astype(np.float32)
loss = engine.train_step([(x, y)])
out = {"rank": os.environ.get("RANK"), "world": jax.process_count(),
       "devices": n, "master": os.environ.get("MASTER_ADDR"),
       "loss": float(jax.device_get(loss))}
with open(os.path.join(sys.argv[1], f"launch_ok_{os.environ.get('RANK', '0')}.json"), "w") as f:
    json.dump(out, f)
'''

FAKE_PDSH = r'''#!/usr/bin/env bash
# fake pdsh for the e2e test: runs the payload locally once per -w host,
# substituting pdsh's %n node-rank token, concurrently (the two "hosts"
# must rendezvous), and propagates failure.
hosts=""; payload=""
while [[ $# -gt 0 ]]; do
  case "$1" in
    -w) hosts="$2"; shift 2;;
    -f) shift 2;;
    *) payload="$1"; shift;;
  esac
done
IFS=',' read -ra HS <<< "$hosts"
pids=()
for i in "${!HS[@]}"; do
  bash -c "${payload//\%n/$i}" &
  pids+=($!)
done
rc=0
for p in "${pids[@]}"; do wait "$p" || rc=1; done
exit $rc
'''


def _launch_env(tmp_path, devices_per_proc):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # exported into spawned processes (and, for pdsh, re-exported by the
        # payload's XLA_/JAX_ prefix rules in collect_env_exports)
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices_per_proc}",
        "DSTPU_REPO": REPO,
    })
    for k in ("MASTER_ADDR", "MASTER_PORT", "WORLD_SIZE", "RANK"):
        env.pop(k, None)
    return env


def _write_train_script(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    return str(script)


def test_runner_single_node_end_to_end(tmp_path):
    """No hostfile -> runner execs launch.py locally -> launch.py sets the
    env contract and spawns the user script, which runs one engine step."""
    script = _write_train_script(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--hostfile", str(tmp_path / "no_such_hostfile"),
         "--master_port", str(free_port()),
         script, str(tmp_path)],
        env=_launch_env(tmp_path, devices_per_proc=4),
        capture_output=True, text=True, timeout=240, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    with open(tmp_path / "launch_ok_0.json") as f:
        out = json.load(f)
    assert out["rank"] == "0"
    assert out["world"] == 1
    assert out["devices"] == 4
    assert out["master"] == "127.0.0.1"


def test_runner_pdsh_two_hosts_end_to_end(tmp_path):
    """Hostfile with two hosts + a fake pdsh: runner builds the pdsh command,
    the payload runs launch.py per node rank, both processes rendezvous via
    jax.distributed (WORLD_SIZE=2) and train one identical engine step."""
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=1\nworker-1 slots=1\n")
    bindir = tmp_path / "bin"
    bindir.mkdir()
    pdsh = bindir / "pdsh"
    pdsh.write_text(FAKE_PDSH)
    pdsh.chmod(pdsh.stat().st_mode | stat.S_IEXEC)

    env = _launch_env(tmp_path, devices_per_proc=2)
    env["PATH"] = f"{bindir}:{env['PATH']}"
    script = _write_train_script(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--hostfile", str(hostfile),
         "--launcher", "pdsh",
         "--master_addr", "127.0.0.1",
         "--master_port", str(free_port()),
         script, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    outs = []
    for rank in (0, 1):
        with open(tmp_path / f"launch_ok_{rank}.json") as f:
            outs.append(json.load(f))
    assert [o["rank"] for o in outs] == ["0", "1"]
    assert all(o["world"] == 2 for o in outs), outs
    assert all(o["devices"] == 4 for o in outs), outs  # 2 procs x 2 devices
    assert outs[0]["loss"] == outs[1]["loss"]


def _mk_args(**over):
    import argparse

    ns = argparse.Namespace(
        launcher_args="", master_port=29500, user_script="train.py",
        user_args=["--flag"],
    )
    for k, v in over.items():
        setattr(ns, k, v)
    return ns


def test_openmpi_runner_cmd():
    from deepspeed_tpu.launcher.multinode_runner import OpenMPIRunner

    world = encode_world_info({"worker-0": [0], "worker-1": [0]})
    r = OpenMPIRunner(_mk_args(), world, "10.0.0.1", {"JAX_PLATFORMS": "tpu"})
    cmd = r.get_cmd()
    assert cmd[:3] == ["mpirun", "-n", "2"]  # one process per host
    assert "--node_rank=OMPI" in " ".join(cmd)
    assert "-x" in cmd and "JAX_PLATFORMS=tpu" in cmd
    assert cmd[-2:] == ["train.py", "--flag"]


def test_mvapich_runner_cmd():
    from deepspeed_tpu.launcher import multinode_runner as mnr

    world = encode_world_info({"worker-0": [0], "worker-1": [0]})
    r = mnr.MVAPICHRunner(_mk_args(), world, "10.0.0.1", {})
    cmd = r.get_cmd()
    assert cmd[:3] == ["mpirun", "-np", "2"]
    hostfile = cmd[cmd.index("-hostfile") + 1]
    with open(hostfile) as f:
        assert f.read().splitlines() == ["worker-0", "worker-1"]
    os.unlink(hostfile)
    joined = " ".join(cmd)
    assert "--node_rank=MPI" in joined
    # Hydra mpiexec two-token form: -env <name> <value>
    i = cmd.index("-env")
    assert "=" not in cmd[i + 1] and cmd[cmd.index("MV2_SUPPORT_DL") + 1] == "1"
    # cuda knobs deliberately absent on TPU
    assert "MV2_USE_CUDA" not in joined
    assert cmd[-2:] == ["train.py", "--flag"]


def test_launch_mpi_rank_discovery(monkeypatch):
    """launch.py resolves --node_rank=MPI from OpenMPI, MVAPICH, or PMI env."""
    from deepspeed_tpu.launcher.launch import mpi_node_rank

    mpi_vars = ("OMPI_COMM_WORLD_RANK", "MV2_COMM_WORLD_RANK", "PMI_RANK")
    for var in mpi_vars:
        for v in mpi_vars:
            monkeypatch.delenv(v, raising=False)
        assert mpi_node_rank() == 0
        monkeypatch.setenv(var, "3")
        assert mpi_node_rank() == 3
