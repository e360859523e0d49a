"""Multi-HOST control plane, end-to-end: two real processes rendezvous via
``deepspeed_tpu.init_distributed`` (the launcher's MASTER_*/RANK/WORLD_SIZE
env contract), form one global mesh, and train through the engine with
ZeRO-2 — losses must be identical across hosts AND equal to a single-process
run over the same global device count.

The reference's distributed tests fork multiprocess NCCL on one box
(tests/unit/common.py); this is the jax.distributed/DCN analogue. Each child
is a separate python process with its own 2-device CPU backend; the global
mesh spans 4 devices across both.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tests.unit.simple_model import free_port

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

CHILD = r'''
import os, sys
sys.path.insert(0, os.environ["DSTPU_REPO"])
import deepspeed_tpu
deepspeed_tpu.init_distributed(verbose=False)
import jax, jax.numpy as jnp, numpy as np
from tests.unit.simple_model import create_simple_model

if os.environ.get("WORLD_SIZE"):
    assert jax.process_count() == int(os.environ["WORLD_SIZE"]), jax.process_count()
assert jax.device_count() == 4, jax.device_count()

model, params = create_simple_model(hidden_dim=8, seed=3)
stage = int(os.environ.get("DSTPU_ZERO", "2"))
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
    config_params={"train_batch_size": 8,
                   "train_micro_batch_size_per_gpu": 2,
                   "gradient_accumulation_steps": 1,
                   "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                   "zero_optimization": {"stage": stage}})
if stage >= 3:
    n_sharded = sum(1 for l in jax.tree_util.tree_leaves(engine.params)
                    if l.sharding.spec and l.sharding.spec[0] == "data")
    assert n_sharded > 0, "zero3 left no param leaf sharded"
rng = np.random.RandomState(0)
losses = []
for i in range(3):
    x = rng.randn(8, 8).astype(np.float32)   # same GLOBAL batch on every host
    y = rng.randn(8, 8).astype(np.float32)
    loss = engine.train_step([(x, y)])
    losses.append(float(jax.device_get(loss)))
print("LOSSES", [round(l, 6) for l in losses])
'''


def _run(rank, world, port, devices, child=CHILD, ckpt=None, zero=0, bf16=False, tp=0):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "DSTPU_REPO": REPO,
    })
    for k in ("MASTER_ADDR", "MASTER_PORT", "WORLD_SIZE", "RANK",
              "DSTPU_CKPT", "DSTPU_ZERO", "DSTPU_BF16", "DSTPU_TP"):
        env.pop(k, None)
    if ckpt:
        env["DSTPU_CKPT"] = ckpt
    if zero:
        env["DSTPU_ZERO"] = str(zero)
    if bf16:
        env["DSTPU_BF16"] = "1"
    if tp:
        env["DSTPU_TP"] = str(tp)
    if world > 1:
        env.update({"MASTER_ADDR": "127.0.0.1", "MASTER_PORT": str(port),
                    "WORLD_SIZE": str(world), "RANK": str(rank)})
    return subprocess.Popen([sys.executable, "-c", child],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True, env=env, cwd=REPO)


def _losses(out):
    for line in out.splitlines():
        if line.startswith("LOSSES "):
            return eval(line[len("LOSSES "):])  # noqa: S307 — our own output
    raise AssertionError(f"no LOSSES line in child output:\n{out[-2000:]}")


@pytest.mark.parametrize("zero", [2, 3])
def test_two_host_engine_matches_single_process(zero):
    """zero=2: grad/optimizer sharding. zero=3: param STORAGE sharded over
    the global data axis (each host holds ~1/4 of every leaf, fp32), the
    gather-on-use all-gathers riding the cross-process fabric."""
    port = free_port()
    procs = [_run(r, 2, port, devices=2, zero=zero) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        # a child stuck in rendezvous (port stolen, peer crashed) must not
        # outlive the test holding the port
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o[-2000:]
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    assert l0 == l1, (l0, l1)

    # single-process oracle: same 4-device global mesh, no DCN
    p = _run(0, 1, port, devices=4, zero=zero)
    try:
        out = p.communicate(timeout=240)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, out[-2000:]
    np.testing.assert_allclose(l0, _losses(out), rtol=1e-5)


PIPE_CHILD = r'''
import os, sys
sys.path.insert(0, os.environ["DSTPU_REPO"])
import deepspeed_tpu
deepspeed_tpu.init_distributed(verbose=False)
import jax, jax.numpy as jnp, numpy as np
import flax.linen as nn
from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule

HID = 8
class Block(nn.Module):
    # ff1/ff2 names take the Megatron column/row TP rules (parallel/tp.py),
    # so the DSTPU_TP variant actually shards the stage params
    @nn.compact
    def __call__(self, x):
        h = jax.nn.relu(nn.Dense(2 * HID, name="ff1")(x))
        return x + nn.Dense(HID, name="ff2")(h)

mod = PipelineModule([LayerSpec(Block) for _ in range(4)], num_stages=2,
                     loss_fn=lambda o, y: jnp.mean((o - y) ** 2),
                     partition_method="uniform")
TP = int(os.environ.get("DSTPU_TP", "1"))
DP = jax.device_count() // 2 // TP  # stages=2
ROWS = 4 * DP
CFG = {
    "train_batch_size": 4 * 2 * DP,
    "train_micro_batch_size_per_gpu": 4,
    "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    # the single-process oracle must run the same executor the multi-host
    # path is forced onto (interpreter==compiled equivalence is asserted in
    # test_pipe_compiled.py)
    "pipeline": {"executor": "compiled"},
}
if TP > 1:
    CFG["tensor_parallel"] = {"size": TP}
if os.environ.get("DSTPU_ZERO"):
    CFG["zero_optimization"] = {"stage": int(os.environ["DSTPU_ZERO"])}
if os.environ.get("DSTPU_BF16"):
    CFG["bf16"] = {"enabled": True}
engine, _, _, _ = deepspeed_tpu.initialize(model=mod, config_params=CFG)
rng = np.random.RandomState(0)
losses = []
for i in range(3):
    data = [(rng.randn(ROWS, HID).astype(np.float32), rng.randn(ROWS, HID).astype(np.float32))
            for _ in range(2)]
    losses.append(round(float(engine.train_batch(iter(data))), 6))
assert engine._compiled is not None, "expected the compiled executor"
if TP > 1:
    assert engine.mp_world_size == TP
    assert any(
        "model" in str(l.sharding.spec)
        for l in jax.tree_util.tree_leaves(engine._compiled["stacked"])
    ), "TP did not shard any stacked stage param"

# multi-host eval: the deterministic compiled loss program (the per-stage
# interpreter cannot cross processes)
erng = np.random.RandomState(123)
eval_data = [(erng.randn(ROWS, HID).astype(np.float32),
              erng.randn(ROWS, HID).astype(np.float32)) for _ in range(2)]
print("EVAL", round(engine.eval_batch(iter(eval_data)), 6))

# checkpoint round trip under multi-host: every rank calls save (the sync's
# allgather is a collective), rank 0 writes; a fresh engine resumes and must
# continue the loss trajectory exactly (Adam moments carried)
ckpt = os.environ.get("DSTPU_CKPT")
if ckpt:
    engine.save_checkpoint(ckpt, tag="mh")
    next_data = [[(rng.randn(8, HID).astype(np.float32),
                   rng.randn(8, HID).astype(np.float32)) for _ in range(2)]
                 for _ in range(2)]
    cont = [round(float(engine.train_batch(iter(d))), 6) for d in next_data]

    mod2 = PipelineModule([LayerSpec(Block) for _ in range(4)], num_stages=2,
                          loss_fn=lambda o, y: jnp.mean((o - y) ** 2),
                          partition_method="uniform")
    e2, _, _, _ = deepspeed_tpu.initialize(model=mod2, config_params=dict(CFG))
    e2.load_checkpoint(ckpt, tag="mh")
    res = [round(float(e2.train_batch(iter(d))), 6) for d in next_data]
    assert res == cont, (res, cont)
print("LOSSES", losses)
'''


def _eval_loss(out):
    for line in out.splitlines():
        if line.startswith("EVAL "):
            return float(line[len("EVAL "):])
    raise AssertionError(f"no EVAL line in child output:\n{out[-2000:]}")


@pytest.mark.parametrize("zero,bf16", [(0, False), (1, False), (1, True)])
def test_two_host_pipeline_matches_single_process(tmp_path, zero, bf16):
    """Pipeline stages SPLIT ACROSS PROCESSES: stage 0 on host A's devices,
    stage 1 on host B's — the ppermute rides the cross-process fabric (the
    reference's multi-node pipeline over NCCL). Multi-host forces the
    compiled executor (host-side staging; per-stage interpreter structures
    cannot cross processes); losses must match a single-process run, and the
    in-child checkpoint round trip (rank-0 writes, all-rank collectives,
    host-side resume) must continue the trajectory exactly."""
    port = free_port()
    procs = [_run(r, 2, port, devices=2, child=PIPE_CHILD,
                  ckpt=str(tmp_path / "mh"), zero=zero, bf16=bf16)
             for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o[-2000:]
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    assert l0 == l1, (l0, l1)

    e0, e1 = _eval_loss(outs[0]), _eval_loss(outs[1])
    assert e0 == e1, (e0, e1)

    p = _run(0, 1, port, devices=4, child=PIPE_CHILD, zero=zero, bf16=bf16)
    try:
        out = p.communicate(timeout=240)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, out[-2000:]
    np.testing.assert_allclose(l0, _losses(out), rtol=1e-4)
    np.testing.assert_allclose(e0, _eval_loss(out), rtol=1e-4)


def test_two_host_pipeline_tensor_parallel(tmp_path):
    """pp2 x tp2 ACROSS two processes: each stage's TP pair spans one host,
    the stage exchange crosses hosts, and the stacked stage params carry the
    model axis — the untested multi-host x compiled x TP combination."""
    port = free_port()
    procs = [_run(r, 2, port, devices=2, child=PIPE_CHILD, tp=2)
             for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o[-2000:]
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    assert l0 == l1, (l0, l1)
    assert _eval_loss(outs[0]) == _eval_loss(outs[1])

    # single-process oracle: same pp2 x tp2 program on a 4-device mesh
    p = _run(0, 1, port, devices=4, child=PIPE_CHILD, tp=2)
    try:
        out = p.communicate(timeout=240)[0]
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, out[-2000:]
    np.testing.assert_allclose(l0, _losses(out), rtol=1e-4)
