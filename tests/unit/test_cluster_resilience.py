"""Job-level (cluster) fault-tolerance tests: worker supervision, preemption,
comm deadlines, health gossip, elastic resume.

The heavy scenarios run REAL subprocess workers under ``WorkerSupervisor`` —
a SIGKILLed or SIGTERMed training process restarted by the supervisor must
resume from the last committed checkpoint tag and reach a **bitwise** final-
param match against an uninterrupted run (same oracle as test_resilience.py,
one level up the stack). Everything is deterministic on CPU: faults fire via
``ClusterFaultInjector`` arms with marker files (one-shot across restarts),
and batches are derived from the step index so any resume replays the exact
clean trajectory.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.comm import comm
from deepspeed_tpu.comm.errors import CommError, CommTimeoutError, DeadPeerError
from deepspeed_tpu.comm.health import HealthGossip
from deepspeed_tpu.elasticity import compute_elastic_resume
from deepspeed_tpu.elasticity.config import (
    ElasticityConfigError,
    ElasticityIncompatibleWorldSize,
)
from deepspeed_tpu.launcher.supervisor import (
    CLASS_CLEAN,
    CLASS_CRASH,
    CLASS_FATAL,
    CLASS_HUNG,
    CLASS_PREEMPTED,
    EXIT_PREEMPTED,
    HEARTBEAT_FILE_ENV,
    PREEMPT_SAVE_DIR_ENV,
    WorkerSupervisor,
    classify_exit,
)
from deepspeed_tpu.runtime.resilience import (
    ClusterFaultInjector,
    PreemptionHandler,
    set_active_injector,
)
from deepspeed_tpu.version import __version__

from simple_model import make_simple_engine

pytestmark = pytest.mark.faults

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
HIDDEN = 16
TOTAL_STEPS = 4
FAULT_STEP = 2

# ---------------------------------------------------------------------------
# WorkerSupervisor units (tiny python -c children; no jax)
# ---------------------------------------------------------------------------

# crash (or preempt) once, then exit clean: the marker file records that the
# first incarnation already failed — exactly how a restarted worker behaves
_FLAKY_CHILD = (
    "import os, sys\n"
    "p = os.environ['FLAKY_MARKER']\n"
    "if os.path.exists(p):\n"
    "    sys.exit(0)\n"
    "open(p, 'w').close()\n"
    "sys.exit(int(os.environ.get('FLAKY_RC', '3')))\n"
)


def _child(code):
    return [sys.executable, "-c", code]


def test_classify_exit():
    assert classify_exit(0) == CLASS_CLEAN
    assert classify_exit(EXIT_PREEMPTED) == CLASS_PREEMPTED
    assert classify_exit(98) == CLASS_FATAL
    assert classify_exit(1) == CLASS_CRASH
    assert classify_exit(-9) == CLASS_CRASH  # signal death
    assert classify_exit(98, fatal_exit_codes=()) == CLASS_CRASH


def test_supervisor_clean_exit_no_restart():
    sup = WorkerSupervisor(_child("pass"), max_restarts=5, backoff_s=0.01)
    assert sup.run() == 0
    assert sup.restarts == 0
    assert sup.exit_history == [(CLASS_CLEAN, 0)]


def test_supervisor_restarts_crash_until_success(tmp_path):
    env = dict(os.environ, FLAKY_MARKER=str(tmp_path / "crashed"), FLAKY_RC="3")
    sup = WorkerSupervisor(_child(_FLAKY_CHILD), env=env,
                           max_restarts=2, backoff_s=0.01)
    assert sup.run() == 0
    assert sup.restarts == 1
    assert sup.exit_history == [(CLASS_CRASH, 3), (CLASS_CLEAN, 0)]


def test_supervisor_fatal_exit_never_restarts():
    sup = WorkerSupervisor(_child("import sys; sys.exit(98)"),
                           max_restarts=5, backoff_s=0.01)
    assert sup.run() == 98
    assert sup.restarts == 0
    assert sup.exit_history == [(CLASS_FATAL, 98)]


def test_supervisor_preempted_restarts_without_backoff(tmp_path):
    """Exit 99 restarts immediately: a crash here would sleep backoff_s=5
    and trip the elapsed bound."""
    env = dict(os.environ, FLAKY_MARKER=str(tmp_path / "preempted"),
               FLAKY_RC=str(EXIT_PREEMPTED))
    sup = WorkerSupervisor(_child(_FLAKY_CHILD), env=env,
                           max_restarts=1, backoff_s=5.0)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 4.0
    assert sup.exit_history == [(CLASS_PREEMPTED, EXIT_PREEMPTED), (CLASS_CLEAN, 0)]


def test_supervisor_budget_exhausted_propagates_rc():
    sup = WorkerSupervisor(_child("import sys; sys.exit(3)"),
                           max_restarts=1, backoff_s=0.01)
    assert sup.run() == 3
    assert sup.restarts == 1
    assert sup.exit_history == [(CLASS_CRASH, 3), (CLASS_CRASH, 3)]


def test_supervisor_kills_worker_with_stale_heartbeat(tmp_path):
    hb = tmp_path / "hb"
    hb.touch()
    sup = WorkerSupervisor(_child("import time; time.sleep(60)"),
                           heartbeat_timeout_s=0.5, heartbeat_file=str(hb),
                           term_grace_s=1.0, max_restarts=0)
    t0 = time.monotonic()
    rc = sup.run()
    assert time.monotonic() - t0 < 10.0  # killed, not waited out
    assert rc != 0
    assert sup.exit_history[0][0] == CLASS_HUNG


def test_supervisor_beating_worker_stays_alive():
    """A worker that beats faster than the timeout outlives many timeout
    windows — mtime refresh really resets the staleness clock."""
    code = (
        "import os, time\n"
        "p = os.environ[%r]\n"
        "for _ in range(12):\n"
        "    os.utime(p, None)\n"
        "    time.sleep(0.1)\n"
    ) % HEARTBEAT_FILE_ENV
    sup = WorkerSupervisor(_child(code), heartbeat_timeout_s=0.5, max_restarts=0)
    assert sup.run() == 0
    assert sup.exit_history == [(CLASS_CLEAN, 0)]


# ---------------------------------------------------------------------------
# supervised end-to-end: kill / preempt a REAL training worker, resume,
# bitwise-match an uninterrupted run
# ---------------------------------------------------------------------------

WORKER_SCRIPT = """\
import os, sys, tempfile
sys.path.insert(0, os.environ["DSTPU_REPO"])
sys.path.insert(0, os.path.join(os.environ["DSTPU_REPO"], "tests", "unit"))
import numpy as np
import jax
from simple_model import make_simple_engine

HIDDEN = 16
ck = os.environ["WORKER_CKPT"]
total = int(os.environ["WORKER_STEPS"])
fault = os.environ.get("WORKER_FAULT", "")
save_every = os.environ.get("WORKER_SAVE_EVERY", "1") == "1"

res = {"max_recoveries": 2, "recovery_backoff_s": 0}
if fault:
    point = {"kill": "kill_worker", "preempt": "preempt_signal"}[fault]
    res["fault_injection"] = {point: {
        "at_step": int(os.environ["WORKER_FAULT_STEP"]),
        "marker": os.environ["WORKER_MARKER"],
    }}
cfg = {"train_batch_size": 8, "steps_per_print": 100,
       "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
       "resilience": res}

eng = make_simple_engine(tempfile.mkdtemp(), cfg)
eng.load_checkpoint(ck)  # fresh dir -> (None, {}): start from step 0

def batch(i):
    # batches keyed on the STEP INDEX: a resumed run replays the clean data
    rng = np.random.default_rng(1000 + i)
    return (rng.standard_normal((8, HIDDEN)).astype(np.float32),
            rng.standard_normal((8, HIDDEN)).astype(np.float32))

while eng.global_steps < total:
    eng.train_batch(iter([batch(eng.global_steps)]))
    if save_every:
        eng.save_checkpoint(ck)

leaves = jax.tree_util.tree_leaves(jax.device_get(eng.params))
np.savez(os.environ["WORKER_OUT"], *[np.asarray(l) for l in leaves])
print("WORKER_DONE", eng.global_steps, flush=True)
"""


def _worker_env(tmp, **extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "DSTPU_REPO": REPO,
        "WORKER_CKPT": str(tmp / "ckpt"),
        "WORKER_OUT": str(tmp / "final.npz"),
        "WORKER_STEPS": str(TOTAL_STEPS),
    })
    for k in (HEARTBEAT_FILE_ENV, PREEMPT_SAVE_DIR_ENV, "DSTPU_PREEMPTION",
              "MASTER_ADDR", "MASTER_PORT", "WORLD_SIZE", "RANK"):
        env.pop(k, None)
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _write_worker(tmp):
    script = tmp / "worker.py"
    script.write_text(WORKER_SCRIPT)
    return str(script)


def _final_params(path):
    with np.load(path) as z:
        return [z[k] for k in z.files]


@pytest.fixture(scope="module")
def clean_final(tmp_path_factory):
    """Final params of an uninterrupted TOTAL_STEPS run (the bitwise oracle
    both fault scenarios compare against)."""
    tmp = tmp_path_factory.mktemp("clean")
    env = _worker_env(tmp)
    proc = subprocess.run([sys.executable, "-u", _write_worker(tmp)],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "WORKER_DONE 4" in proc.stdout
    return _final_params(tmp / "final.npz")


def test_killed_worker_resumes_to_bitwise_match(tmp_path, clean_final):
    """SIGKILL (hard death, no cleanup) at step 2 under the supervisor:
    restart + resume from the last committed tag must reproduce the clean
    trajectory EXACTLY."""
    env = _worker_env(tmp_path, WORKER_FAULT="kill",
                      WORKER_FAULT_STEP=FAULT_STEP,
                      WORKER_MARKER=tmp_path / "killed")
    sup = WorkerSupervisor([sys.executable, "-u", _write_worker(tmp_path)],
                           env=env, max_restarts=2, backoff_s=0.01)
    assert sup.run() == 0
    assert sup.restarts == 1
    assert sup.exit_history == [(CLASS_CRASH, -signal.SIGKILL), (CLASS_CLEAN, 0)]
    got = _final_params(tmp_path / "final.npz")
    assert len(got) == len(clean_final)
    assert all(np.array_equal(a, b) for a, b in zip(got, clean_final))


def test_preempted_worker_commits_emergency_checkpoint_and_resumes(tmp_path, clean_final):
    """SIGTERM at step 2 with NO periodic checkpoints: the ONLY state that
    can carry the run across the restart is the PreemptionHandler's
    emergency checkpoint + EXIT_PREEMPTED — and it must, bitwise."""
    ck = tmp_path / "ckpt"
    env = _worker_env(tmp_path, WORKER_FAULT="preempt",
                      WORKER_FAULT_STEP=FAULT_STEP,
                      WORKER_MARKER=tmp_path / "preempted",
                      WORKER_SAVE_EVERY="0",
                      **{PREEMPT_SAVE_DIR_ENV: ck})
    sup = WorkerSupervisor([sys.executable, "-u", _write_worker(tmp_path)],
                           env=env, max_restarts=2, backoff_s=5.0)
    t0 = time.monotonic()
    assert sup.run() == 0
    # preempted restarts skip the 5s crash backoff
    assert sup.exit_history == [(CLASS_PREEMPTED, EXIT_PREEMPTED), (CLASS_CLEAN, 0)]
    assert sup.restarts == 1
    # the emergency commit landed under the preemption save dir at the
    # interrupted step boundary
    assert (ck / f"global_step{FAULT_STEP}").is_dir()
    got = _final_params(tmp_path / "final.npz")
    assert all(np.array_equal(a, b) for a, b in zip(got, clean_final))
    assert time.monotonic() - t0 < 280


def test_preemption_handler_in_process(tmp_path):
    """Signal -> flag -> emergency checkpoint at the step boundary ->
    SystemExit(EXIT_PREEMPTED), without a subprocess in the loop."""
    (tmp_path / "e").mkdir()
    eng = make_simple_engine(tmp_path / "e", {
        "train_batch_size": 8, "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    })
    handler = PreemptionHandler(eng, save_dir=str(tmp_path / "emerg")).install()
    try:
        assert not handler.requested
        handler.check()  # no signal yet: no-op
        os.kill(os.getpid(), signal.SIGTERM)
        # the python-level handler runs at the next bytecode boundary
        deadline = time.monotonic() + 5
        while not handler.requested and time.monotonic() < deadline:
            time.sleep(0.01)
        assert handler.requested
        with pytest.raises(SystemExit) as ei:
            handler.check()
        assert ei.value.code == EXIT_PREEMPTED
        assert (tmp_path / "emerg" / handler.emergency_tag).is_dir()
    finally:
        handler.uninstall()


# ---------------------------------------------------------------------------
# comm deadlines (hang_barrier arm drives the CommTimeoutError path)
# ---------------------------------------------------------------------------

def test_barrier_timeout_raises_within_deadline():
    ClusterFaultInjector({"hang_barrier": {"seconds": 30.0, "times": 2}})
    try:
        t0 = time.monotonic()
        with pytest.raises(CommTimeoutError):
            comm.barrier("wedged", timeout_s=0.3)
        assert time.monotonic() - t0 < 5.0  # surfaced near the deadline, not at 30s
        with pytest.raises(CommTimeoutError):
            comm.host_allreduce_scalar(1.0, timeout_s=0.3)
    finally:
        set_active_injector(None)


def test_barrier_with_deadline_still_completes_unwedged():
    assert comm.barrier("healthy", timeout_s=30.0) is None
    assert comm.host_allreduce_scalar(2.5, timeout_s=30.0) == 2.5


def test_comm_timeout_bounds_checkpoint_commit_barrier(tmp_path):
    """`resilience.comm_timeout_s` bounds the engine's checkpoint-commit
    rendezvous: a wedged barrier surfaces as CommTimeoutError within the
    deadline, and the tag itself (committed before the barrier) survives."""
    (tmp_path / "e").mkdir()
    eng = make_simple_engine(tmp_path / "e", {
        "train_batch_size": 8, "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "resilience": {"max_recoveries": 2, "recovery_backoff_s": 0,
                       "comm_timeout_s": 0.3,
                       "fault_injection": {"hang_barrier": {"seconds": 30.0}}},
    })
    try:
        t0 = time.monotonic()
        with pytest.raises(CommTimeoutError):
            eng.save_checkpoint(str(tmp_path / "ck"))
        assert time.monotonic() - t0 < 5.0
        # the hang arm is exhausted (times=1): the next commit goes through
        assert eng.save_checkpoint(str(tmp_path / "ck"))
    finally:
        set_active_injector(None)


def test_comm_timeout_error_taxonomy():
    e = CommTimeoutError(what="barrier 'x'", timeout_s=1.5)
    assert isinstance(e, TimeoutError) and isinstance(e, CommError)
    assert "barrier 'x'" in str(e) and "1.5" in str(e)
    d = DeadPeerError(rank=3, stale_s=7.0, timeout_s=2.0)
    assert isinstance(d, CommError)
    assert d.rank == 3 and "restart" in str(d)


# ---------------------------------------------------------------------------
# health gossip
# ---------------------------------------------------------------------------

def test_health_gossip_detects_dead_peer(tmp_path):
    a = HealthGossip(str(tmp_path), rank=0, world_size=2, peer_timeout_s=0.2)
    b = HealthGossip(str(tmp_path), rank=1, world_size=2, peer_timeout_s=0.2)
    a.check_peers()
    b.check_peers()  # both freshly beaten: healthy
    time.sleep(0.35)  # rank 1 goes silent
    a.beat()
    with pytest.raises(DeadPeerError) as ei:
        a.check_peers()
    assert ei.value.rank == 1
    assert ei.value.stale_s > 0.2
    b.beat()  # the "dead" host coming back clears the verdict
    a.check_peers()


def test_health_gossip_startup_grace(tmp_path):
    """Peers that have not written their first beat are measured from OUR
    start — booting hosts must not be declared dead on skew."""
    g = HealthGossip(str(tmp_path), rank=0, world_size=4, peer_timeout_s=5.0)
    assert g.stale_peers() == []
    assert g.last_seen(2) < 1.0


def test_dead_peer_arm_suppresses_heartbeat(tmp_path, monkeypatch):
    """The dead_peer arm silences this host's liveness signals from the
    armed step on: the supervisor-facing heartbeat stops beating while
    training itself continues."""
    hb = tmp_path / "hb"
    hb.touch()
    monkeypatch.setenv(HEARTBEAT_FILE_ENV, str(hb))
    monkeypatch.delenv("DSTPU_PREEMPTION", raising=False)
    (tmp_path / "e").mkdir()
    eng = make_simple_engine(tmp_path / "e", {
        "train_batch_size": 8, "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "resilience": {"max_recoveries": 2, "recovery_backoff_s": 0,
                       "fault_injection": {"dead_peer": {"at_step": 1}}},
    })
    try:
        rng = np.random.default_rng(0)
        for _ in range(3):
            x = rng.standard_normal((8, HIDDEN)).astype(np.float32)
            y = rng.standard_normal((8, HIDDEN)).astype(np.float32)
            eng.train_batch(iter([(x, y)]))
        hooks = eng._cluster
        assert hooks.heartbeat is not None
        assert hooks.heartbeat.beats == 1  # step 0 beat; steps 1..2 silenced
        assert eng.resilience.injector.fired.get("dead_peer") == 1
    finally:
        set_active_injector(None)


# ---------------------------------------------------------------------------
# elastic resume
# ---------------------------------------------------------------------------

ELASTIC = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 48,
        "micro_batch_sizes": [1, 2, 4, 8],
        "min_gpus": 1,
        "max_gpus": 64,
        "version": 0.1,
        "ignore_non_elastic_batch_info": True,
    }
}


def test_elastic_resume_preserves_global_batch():
    plan = compute_elastic_resume(ELASTIC, __version__,
                                  prev_world_size=4, new_world_size=8,
                                  saved_train_batch_size=48)
    assert plan["train_batch_size"] == 48  # the invariant: global batch fixed
    assert (plan["micro_batch_size"] * plan["gradient_accumulation_steps"] * 8
            == plan["train_batch_size"])
    assert 8 in plan["valid_gpus"]


def test_elastic_resume_invalid_world_size_raises():
    with pytest.raises(ElasticityIncompatibleWorldSize):
        compute_elastic_resume(ELASTIC, __version__,
                               prev_world_size=8, new_world_size=5)


def test_elastic_resume_rejects_changed_global_batch():
    with pytest.raises(ElasticityConfigError, match="changed between runs"):
        compute_elastic_resume(ELASTIC, __version__,
                               prev_world_size=4, new_world_size=8,
                               saved_train_batch_size=32)


def test_engine_elastic_resume_resplits_preserved_batch(tmp_path):
    cfg = {"optimizer": {"type": "Adam", "params": {"lr": 0.01}},
           "steps_per_print": 100, **ELASTIC}
    eng = make_simple_engine(tmp_path, cfg)
    assert eng.elasticity_enabled()
    assert eng.train_batch_size() == 48
    # checkpoint from a 4-rank run restarting on these 8 ranks
    eng._maybe_elastic_resume({"dp_world_size": 4, "train_batch_size": 48})
    assert eng.train_batch_size() == 48
    assert (eng.train_micro_batch_size_per_gpu()
            * eng.gradient_accumulation_steps() * eng.dp_world_size == 48)
    # a checkpoint whose global batch the current elastic config cannot
    # reproduce must refuse to resume
    with pytest.raises(ElasticityConfigError):
        eng._maybe_elastic_resume({"dp_world_size": 4, "train_batch_size": 32})


def test_engine_without_elasticity_warns_but_resumes(tmp_path):
    eng = make_simple_engine(tmp_path, {
        "train_batch_size": 8, "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    })
    before = eng.train_batch_size()
    eng._maybe_elastic_resume({"dp_world_size": 4, "train_batch_size": 8})
    assert eng.train_batch_size() == before  # reference behavior: warn only


# ---------------------------------------------------------------------------
# launcher: node_rank validation, exit-code propagation, runner hygiene
# ---------------------------------------------------------------------------

def _mk_args(**over):
    import argparse

    ns = argparse.Namespace(
        launcher_args="", master_port=29500, user_script="train.py",
        user_args=["--flag"],
    )
    for k, v in over.items():
        setattr(ns, k, v)
    return ns


def test_launch_rejects_out_of_range_node_rank(monkeypatch):
    from deepspeed_tpu.launcher import launch
    from deepspeed_tpu.launcher.runner import encode_world_info

    world = encode_world_info({"host-0": [0]})
    monkeypatch.setattr(sys, "argv", [
        "launch.py", f"--world_info={world}", "--node_rank=5", "train.py"])
    with pytest.raises(SystemExit) as ei:
        launch.main()
    assert ei.value.code == 2


def test_launch_propagates_child_exit_code(tmp_path, monkeypatch):
    from deepspeed_tpu.launcher import launch
    from deepspeed_tpu.launcher.runner import encode_world_info

    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(7)\n")
    world = encode_world_info({"host-0": [0]})
    monkeypatch.setattr(sys, "argv", [
        "launch.py", f"--world_info={world}", "--node_rank=0", str(script)])
    with pytest.raises(SystemExit) as ei:
        launch.main()
    assert ei.value.code == 7  # the child's ACTUAL code, not a generic 1


def test_ssh_runner_propagates_first_nonzero_status(tmp_path):
    """The generated bash waits on each ssh pid individually — one failed
    node fails the launch (a bare `wait` returns 0 and swallowed it)."""
    from deepspeed_tpu.launcher.multinode_runner import SSHRunner
    from deepspeed_tpu.launcher.runner import encode_world_info

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    ssh = fake_bin / "ssh"
    ssh.write_text('#!/bin/sh\ncase "$1" in\n  failhost) exit 7 ;;\nesac\nexit 0\n')
    ssh.chmod(0o755)
    env = dict(os.environ, PATH=f"{fake_bin}:{os.environ['PATH']}")

    world = encode_world_info({"okhost": [0], "failhost": [0]})
    cmd = SSHRunner(_mk_args(), world, "10.0.0.1").get_cmd()
    assert subprocess.run(cmd, env=env, capture_output=True).returncode == 7

    world_ok = encode_world_info({"okhost": [0], "otherhost": [0]})
    cmd = SSHRunner(_mk_args(), world_ok, "10.0.0.1").get_cmd()
    assert subprocess.run(cmd, env=env, capture_output=True).returncode == 0


def test_mvapich_runner_cleans_up_hostfile():
    from deepspeed_tpu.launcher import multinode_runner as mnr
    from deepspeed_tpu.launcher.runner import encode_world_info

    world = encode_world_info({"worker-0": [0], "worker-1": [0]})
    r = mnr.MVAPICHRunner(_mk_args(), world, "10.0.0.1", {})
    cmd = r.get_cmd()
    hostfile = cmd[cmd.index("-hostfile") + 1]
    assert os.path.exists(hostfile)
    r.cleanup()
    assert not os.path.exists(hostfile)
    r.cleanup()  # idempotent: second cleanup tolerates the missing file
