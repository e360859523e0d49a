"""Checkpoint save/load round-trips (model: reference tests/unit/test_checkpointing.py)."""

import numpy as np
import pytest

import jax

from tests.unit.simple_model import make_simple_engine, random_dataloader


def _cfg(zero_stage=0, fp16=False, scheduler=False):
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    if fp16:
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    if zero_stage:
        cfg["zero_optimization"] = {"stage": zero_stage}
    if scheduler:
        cfg["scheduler"] = {"type": "WarmupLR", "params": {"warmup_min_lr": 0, "warmup_max_lr": 0.01, "warmup_num_steps": 10}}
    return cfg


def _train_steps(engine, steps, seed=3):
    loader = random_dataloader(engine, total_samples=steps * engine.train_batch_size(), hidden_dim=16, seed=seed)
    for x, y in loader:
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    return loss


def _tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(jax.device_get(a))
    fb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32), rtol=1e-6)


@pytest.mark.parametrize("zero_stage,fp16", [(0, False), (0, True), (1, True), (2, True)])
def test_checkpoint_roundtrip(tmpdir, zero_stage, fp16):
    save_dir = str(tmpdir.join("ckpt"))
    cfg = _cfg(zero_stage=zero_stage, fp16=fp16)

    engine = make_simple_engine(tmpdir, cfg)
    _train_steps(engine, 4)
    engine.save_checkpoint(save_dir)
    saved_params = jax.device_get(engine.params)
    saved_steps = engine.global_steps

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)  # different init
    tag, client = engine2.load_checkpoint(save_dir)
    assert tag is not None
    assert engine2.global_steps == saved_steps
    _tree_equal(engine2.params, saved_params)

    # Continued training from the two engines must match exactly.
    l1 = _train_steps(engine, 3, seed=17)
    l2 = _train_steps(engine2, 3, seed=17)
    np.testing.assert_allclose(float(jax.device_get(l1)), float(jax.device_get(l2)), rtol=1e-5)


def test_checkpoint_latest_tag(tmpdir):
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, _cfg())
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="tag_a")
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="tag_b")
    with open(f"{save_dir}/latest") as f:
        assert f.read().strip() == "tag_b"
    engine2 = make_simple_engine(tmpdir, _cfg(), seed=42)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "tag_b" in name


def test_checkpoint_client_state(tmpdir):
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, _cfg())
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, client_state={"epoch": 7, "note": "hello"})
    engine2 = make_simple_engine(tmpdir, _cfg(), seed=42)
    _, client = engine2.load_checkpoint(save_dir)
    assert client["epoch"] == 7
    assert client["note"] == "hello"


def test_checkpoint_lr_scheduler(tmpdir):
    save_dir = str(tmpdir.join("ckpt"))
    cfg = _cfg(scheduler=True)
    engine = make_simple_engine(tmpdir, cfg)
    _train_steps(engine, 4)
    it = engine.lr_scheduler.last_batch_iteration
    engine.save_checkpoint(save_dir)
    engine2 = make_simple_engine(tmpdir, cfg, seed=42)
    engine2.load_checkpoint(save_dir)
    assert engine2.lr_scheduler.last_batch_iteration == it


def test_checkpoint_missing_dir(tmpdir):
    engine = make_simple_engine(tmpdir, _cfg())
    name, client = engine.load_checkpoint(str(tmpdir.join("nope")))
    assert name is None
    assert client == {}


def test_zero_offload_checkpoint_roundtrip(tmpdir):
    """Offload checkpoints must capture the HOST master, and training must
    continue identically after reload."""
    save_dir = str(tmpdir.join("ckpt"))
    cfg = _cfg(zero_stage=2, fp16=True)
    cfg["zero_optimization"]["cpu_offload"] = True

    engine = make_simple_engine(tmpdir, cfg)
    _train_steps(engine, 4)
    engine.save_checkpoint(save_dir)

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    engine2.load_checkpoint(save_dir)
    _tree_equal(engine2.params, jax.device_get(engine.params))

    l1 = _train_steps(engine, 3, seed=21)
    l2 = _train_steps(engine2, 3, seed=21)
    np.testing.assert_allclose(float(jax.device_get(l1)), float(jax.device_get(l2)), rtol=1e-4)


def test_zero_offload_streamed_checkpoint_resume_bitwise(tmpdir):
    """Checkpoint-under-offload with the bucket-streamed pipeline: a save
    taken mid-stream (_host_shard_state_dicts) must resume EXACTLY — into a
    streamed engine and into an unstreamed (K=1) one — landing bitwise on
    the uninterrupted run. fp32 compute so 'exact' means array_equal."""
    save_dir = str(tmpdir.join("ckpt"))
    cfg = _cfg(zero_stage=2)
    cfg["zero_optimization"]["cpu_offload"] = True
    cfg["zero_optimization"]["offload_stream_buckets"] = 3

    engine = make_simple_engine(tmpdir, cfg)
    _train_steps(engine, 4)
    engine.save_checkpoint(save_dir)

    resumed = {}
    for label, k in (("streamed", 3), ("sequential", 1)):
        c = _cfg(zero_stage=2)
        c["zero_optimization"]["cpu_offload"] = True
        c["zero_optimization"]["offload_stream_buckets"] = k
        e = make_simple_engine(tmpdir, c, seed=99)
        tag, _ = e.load_checkpoint(save_dir)
        assert tag is not None
        # host-resident Adam state restored exactly, not just params
        hs = e.optimizer.inner._host_state
        ref = engine.optimizer.inner._host_state
        assert hs.step == ref.step
        np.testing.assert_array_equal(hs.exp_avg, ref.exp_avg)
        np.testing.assert_array_equal(hs.exp_avg_sq, ref.exp_avg_sq)
        resumed[label] = e

    _train_steps(engine, 3, seed=21)
    for e in resumed.values():
        _train_steps(e, 3, seed=21)
    for e in resumed.values():
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(engine.params)),
                        jax.tree_util.tree_leaves(jax.device_get(e.params))):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        resumed["streamed"].optimizer._host_master,
        resumed["sequential"].optimizer._host_master)


def test_zero_checkpoint_save_before_step(tmpdir):
    """Saving immediately after initialize (before any step) must work."""
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, _cfg(zero_stage=1, fp16=True))
    assert engine.save_checkpoint(save_dir)


def _cfg_dp(zero_stage, dp, variant):
    """Config pinned to an explicit dp degree (mesh.data_parallel_size) so
    save and load can run at different degrees on the one 8-device pool."""
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "mesh": {"data_parallel_size": dp},
        "zero_optimization": {"stage": zero_stage},
    }
    if variant in ("fp16", "offload"):
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    elif variant == "bf16":
        cfg["bf16"] = {"enabled": True}
    if variant == "offload":
        cfg["zero_optimization"]["cpu_offload"] = True
    return cfg


def _merged_master(engine):
    """Concatenate the engine's logical ZeRO master shards (unpadded)."""
    shards = engine.optimizer.shard_state_dicts(engine.opt_state)
    if shards[0].get("master_from_params"):
        return None
    return np.concatenate([np.asarray(s["flat_master"], np.float32) for s in shards])


@pytest.mark.parametrize(
    "zero_stage,load_dp,variant",
    [
        (1, 2, "fp16"),
        (2, 2, "fp16"),
        (2, 8, "fp16"),
        (2, 2, "offload"),
        (2, 2, "bf16"),
        (2, 8, "fp32"),
    ],
)
def test_zero_elastic_checkpoint_cross_dp(tmpdir, zero_stage, load_dp, variant):
    """Elastic ZeRO resume at a CHANGED dp degree (save dp=4, load dp=2/8):
    the saved per-rank shards are merged and re-partitioned for the new
    degree (sharded_optimizer.load_shard_state_dicts; reference mechanism
    runtime/zero/stage2.py:1648-1841, covered by the reference's
    tests/unit/test_checkpointing.py elastic cases)."""
    save_dir = str(tmpdir.join("ckpt"))
    cfg_save = _cfg_dp(zero_stage, dp=4, variant=variant)

    engine = make_simple_engine(tmpdir, cfg_save)
    assert engine.dp_world_size == 4
    _train_steps(engine, 4)
    engine.save_checkpoint(save_dir)
    saved_params = jax.device_get(engine.params)
    saved_master = _merged_master(engine)

    cfg_load = _cfg_dp(zero_stage, dp=load_dp, variant=variant)
    engine2 = make_simple_engine(tmpdir, cfg_load, seed=99)  # different init
    assert engine2.dp_world_size == load_dp
    tag, _ = engine2.load_checkpoint(save_dir)
    assert tag is not None
    _tree_equal(engine2.params, saved_params)
    if saved_master is not None:
        # the re-partitioned master must be the SAME logical vector
        np.testing.assert_allclose(_merged_master(engine2), saved_master, rtol=0, atol=0)

    # Continued training must match the never-stopped oracle (same data).
    l1 = _train_steps(engine, 3, seed=17)
    l2 = _train_steps(engine2, 3, seed=17)
    rtol = 2e-3 if variant == "bf16" else 1e-4
    np.testing.assert_allclose(
        float(jax.device_get(l1)), float(jax.device_get(l2)), rtol=rtol
    )


def _moments(engine):
    """Adam's moments as the engine's logical (unpadded) shards merge."""
    shards = engine.optimizer.shard_state_dicts(engine.opt_state)
    merged = [np.concatenate([np.atleast_1d(s["inner"][i]) for s in shards])
              for i in range(len(shards[0]["inner"]))]
    return [m for m in merged if m.shape[0] == shards[0]["numel"]]


@pytest.mark.parametrize("save_rule,load_rule", [("dp", "dp_x_128"),
                                                 ("dp_x_128", "dp")])
@pytest.mark.parametrize("save_dp,load_dp", [(4, 4), (2, 4)])
@pytest.mark.parametrize("variant", ["bf16", "offload"])
def test_zero_checkpoint_crosses_the_flat_vectors_padding(
        tmpdir, monkeypatch, save_rule, load_rule, save_dp, load_dp, variant):
    """A checkpoint written while the flat vector was padded to ``dp`` alone
    loads into today's layout (``dp`` whole lane tiles), and the reverse:
    shards are cut at ``padded // dp`` and stored unpadded up to ``numel``,
    so the padding is never in a file. SimpleModel's 544 elements under
    ``dp`` 4 leave the last rank's shard wholly past ``numel`` today: it
    stores an empty slice."""
    import contextlib

    from deepspeed_tpu.runtime.zero import sharded_optimizer as so

    rules = {"dp": lambda dp: dp, "dp_x_128": so.flat_pad_multiple}
    save_dir = str(tmpdir.join("ckpt"))

    @contextlib.contextmanager
    def padded_by(rule):
        # read at init and whenever a step is traced
        with monkeypatch.context() as m:
            m.setattr(so, "flat_pad_multiple", rules[rule])
            yield

    with padded_by(save_rule):
        engine = make_simple_engine(tmpdir, _cfg_dp(2, save_dp, variant))
        _train_steps(engine, 3)
    numel, multiple = engine.optimizer._numel, rules[save_rule](save_dp)
    assert engine.optimizer._padded == -(-numel // multiple) * multiple
    shards = engine.optimizer.shard_state_dicts(engine.opt_state)
    assert sum(s["flat_master"].shape[0] for s in shards) == numel
    if save_rule == "dp_x_128" and save_dp == 4:
        assert shards[-1]["flat_master"].shape[0] == 0
    engine.save_checkpoint(save_dir)
    saved_params = jax.device_get(engine.params)
    saved_master, saved_moments = _merged_master(engine), _moments(engine)
    assert len(saved_moments) == 2

    with padded_by(load_rule):
        engine2 = make_simple_engine(                       # different init
            tmpdir, _cfg_dp(2, load_dp, variant), seed=99)
        tag, _ = engine2.load_checkpoint(save_dir)
    assert tag is not None
    assert engine2.optimizer._padded != engine.optimizer._padded
    _tree_equal(engine2.params, saved_params)
    np.testing.assert_array_equal(_merged_master(engine2), saved_master)
    for got, want in zip(_moments(engine2), saved_moments):
        np.testing.assert_array_equal(got, want)
    if variant != "offload":
        # whole tiles a rank, zeros behind numel
        master = np.asarray(engine2.opt_state.flat_master)
        assert master.shape[0] == engine2.optimizer._padded
        assert not master[numel:].any()

    with padded_by(save_rule):
        l1 = _train_steps(engine, 3, seed=17)
    with padded_by(load_rule):
        l2 = _train_steps(engine2, 3, seed=17)
    np.testing.assert_allclose(float(jax.device_get(l1)),
                               float(jax.device_get(l2)), rtol=2e-3)


def test_zero_checkpoint_shard_files(tmpdir):
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, _cfg(zero_stage=2, fp16=True))
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="z")
    import glob

    shards = glob.glob(f"{save_dir}/z/zero_pp_rank_*optim_states.pt")
    assert len(shards) == engine.dp_world_size


# ---------------------------------------------------------------------------
# Fault-injection suite: the atomic-commit protocol must survive a crash at
# EVERY write stage, torn/corrupted shards, and a deleted `latest` pointer
# (runtime/checkpoint/: storage + manifest + fault_injection).
# ---------------------------------------------------------------------------

import os

from deepspeed_tpu.runtime.checkpoint import (
    MANIFEST_NAME,
    CheckpointCorruptionError,
    InjectedCrash,
    read_manifest,
)


def _cfg_ft(**ckpt):
    """_cfg() + a checkpoint section with an armed-able injector and
    zero retry backoff (tests should not sleep)."""
    cfg = _cfg()
    ckpt.setdefault("retry_backoff_s", 0)
    ckpt.setdefault("fault_injection", {})
    cfg["checkpoint"] = ckpt
    return cfg


def _save_good_tag(tmpdir, cfg, tag="one"):
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag=tag)
    return engine, save_dir, jax.device_get(engine.params), engine.global_steps


def _module_states_file(save_dir, tag):
    """The module-states file of a tag, via its manifest inventory."""
    manifest = read_manifest(os.path.join(save_dir, tag))
    (name,) = [n for n in manifest["files"] if "model_states" in n]
    return os.path.join(save_dir, tag, name)


@pytest.mark.parametrize(
    "point", ["tmp_write", "fsync", "rename", "manifest_write", "manifest_rename"]
)
@pytest.mark.faults
def test_ckpt_crash_at_every_write_stage_falls_back(tmpdir, point):
    """A simulated preemption at any stage of the save leaves the previous
    committed tag loadable: manifest.json lands last, so the half-written
    tag is simply never a candidate."""
    cfg = _cfg_ft()
    engine, save_dir, params_one, steps_one = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.checkpoint_storage.fault_injector.arm(point, mode="crash")
    with pytest.raises(InjectedCrash):
        engine.save_checkpoint(save_dir, tag="two")
    engine.checkpoint_storage.fault_injector.disarm()
    assert read_manifest(os.path.join(save_dir, "two")) is None  # uncommitted

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert name is not None and "one" in name
    assert engine2.global_steps == steps_one
    _tree_equal(engine2.params, params_one)


@pytest.mark.faults
def test_ckpt_torn_tmp_write_falls_back(tmpdir):
    """Crash after exactly N bytes of a shard reached the .tmp file: the
    torn prefix never reaches the final name, the tag never commits."""
    cfg = _cfg_ft()
    engine, save_dir, params_one, _ = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.checkpoint_storage.fault_injector.arm("tmp_write", after_bytes=16)
    with pytest.raises(InjectedCrash):
        engine.save_checkpoint(save_dir, tag="two")
    engine.checkpoint_storage.fault_injector.disarm()

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "one" in name
    _tree_equal(engine2.params, params_one)


@pytest.mark.faults
def test_ckpt_transient_eio_is_retried(tmpdir):
    """Transient EIO (flaky mount) heals under bounded retry: the save
    commits and round-trips; the injector counts the retried hits."""
    cfg = _cfg_ft(max_retries=3)
    engine, save_dir, params_one, steps_one = _save_good_tag(tmpdir, cfg)
    fi = engine.checkpoint_storage.fault_injector
    fi.arm("tmp_write", mode="transient", times=2)
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="two")
    assert fi.fired["tmp_write"] == 2

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    fi2 = engine2.checkpoint_storage.fault_injector
    fi2.arm("read", mode="transient", times=1)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "two" in name
    assert fi2.fired["read"] == 1
    _tree_equal(engine2.params, jax.device_get(engine.params))


@pytest.mark.faults
def test_ckpt_truncated_shard_falls_back(tmpdir):
    """A committed tag whose shard got truncated after the fact (partial
    replication, disk loss) fails size verification and falls back."""
    cfg = _cfg_ft()
    engine, save_dir, params_one, steps_one = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="two")
    path = _module_states_file(save_dir, "two")
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "one" in name
    assert engine2.global_steps == steps_one
    _tree_equal(engine2.params, params_one)


@pytest.mark.faults
def test_ckpt_corrupt_checksum_falls_back(tmpdir):
    """Same-size bit rot passes the shallow size check but fails the
    read-time crc32/sha256 verification — fall back, don't load garbage."""
    cfg = _cfg_ft()
    engine, save_dir, params_one, _ = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="two")
    path = _module_states_file(save_dir, "two")
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "one" in name
    _tree_equal(engine2.params, params_one)


@pytest.mark.faults
def test_ckpt_deleted_latest_loads_newest_committed(tmpdir):
    """`latest` is a derived convenience, not a single point of failure:
    with it deleted, load resolves the newest committed tag by manifest
    sequence."""
    cfg = _cfg_ft()
    engine, save_dir, _, _ = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.save_checkpoint(save_dir, tag="two")
    os.remove(os.path.join(save_dir, "latest"))

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "two" in name
    _tree_equal(engine2.params, jax.device_get(engine.params))

    # and the manifest is a sane, self-describing commit record
    manifest = read_manifest(os.path.join(save_dir, "two"))
    assert manifest["format_version"] == 1
    assert manifest["sequence"] == 2
    for entry in manifest["files"].values():
        assert entry["bytes"] > 0 and entry["crc32"] and entry["sha256"]


@pytest.mark.faults
def test_ckpt_crash_between_commit_and_latest(tmpdir):
    """A crash AFTER the manifest commit but BEFORE the `latest` update
    leaves a stale hint — the newest committed tag must still win (load
    order is derived from manifest sequences, not the hint)."""
    cfg = _cfg_ft()
    engine, save_dir, _, _ = _save_good_tag(tmpdir, cfg)
    _train_steps(engine, 2)
    engine.checkpoint_storage.fault_injector.arm("latest_write", mode="crash")
    with pytest.raises(InjectedCrash):
        engine.save_checkpoint(save_dir, tag="two")
    engine.checkpoint_storage.fault_injector.disarm()
    assert open(os.path.join(save_dir, "latest")).read().strip() == "one"  # stale

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "two" in name
    _tree_equal(engine2.params, jax.device_get(engine.params))


@pytest.mark.faults
def test_ckpt_all_candidates_corrupt_raises_named_error(tmpdir):
    """When every candidate fails verification the engine raises the
    named corruption error instead of a bare unpickling traceback."""
    cfg = _cfg_ft()
    engine, save_dir, _, _ = _save_good_tag(tmpdir, cfg)
    path = _module_states_file(save_dir, "one")
    with open(path, "wb") as f:
        f.write(b"not a pickle")

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    with pytest.raises(CheckpointCorruptionError):
        engine2.load_checkpoint(save_dir)


@pytest.mark.faults
def test_ckpt_rotation_keeps_newest_committed(tmpdir):
    """keep_last_k=2 across 5 saves leaves exactly the 2 newest committed
    tags — and a corrupted newest still resumes from the older survivor."""
    cfg = _cfg_ft(keep_last_k=2)
    save_dir = str(tmpdir.join("ckpt"))
    engine = make_simple_engine(tmpdir, cfg)
    snapshots = {}
    for i in range(1, 6):
        _train_steps(engine, 1)
        engine.save_checkpoint(save_dir, tag=f"t{i}")
        snapshots[f"t{i}"] = (jax.device_get(engine.params), engine.global_steps)

    tag_dirs = sorted(
        d for d in os.listdir(save_dir) if os.path.isdir(os.path.join(save_dir, d))
    )
    assert tag_dirs == ["t4", "t5"]

    # corrupt the newest -> resume lands on t4, the older committed tag
    path = _module_states_file(save_dir, "t5")
    with open(path, "wb") as f:
        f.write(b"garbage")
    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "t4" in name
    params_t4, steps_t4 = snapshots["t4"]
    assert engine2.global_steps == steps_t4
    _tree_equal(engine2.params, params_t4)


@pytest.mark.faults
def test_ckpt_rotation_spares_uncommitted_dirs(tmpdir):
    """Only committed tags rotate: an uncommitted (crashed) save and
    foreign files in the checkpoint root are never deleted."""
    cfg = _cfg_ft(keep_last_k=1)
    engine, save_dir, _, _ = _save_good_tag(tmpdir, cfg, tag="good")
    engine.checkpoint_storage.fault_injector.arm("manifest_rename", mode="crash")
    with pytest.raises(InjectedCrash):
        engine.save_checkpoint(save_dir, tag="crashed")
    engine.checkpoint_storage.fault_injector.disarm()
    _train_steps(engine, 1)
    engine.save_checkpoint(save_dir, tag="good2")  # rotates "good" out

    dirs = {d for d in os.listdir(save_dir) if os.path.isdir(os.path.join(save_dir, d))}
    assert "good" not in dirs          # rotated (committed, beyond k=1)
    assert "crashed" in dirs           # uncommitted: never touched
    assert "good2" in dirs             # newest committed: never deleted


@pytest.mark.faults
def test_ckpt_legacy_tag_without_manifest_loads(tmpdir):
    """Pre-subsystem checkpoints (no manifest.json) stay loadable through
    the `latest` hint — no verification, but no regression either."""
    cfg = _cfg_ft()
    engine, save_dir, params_one, steps_one = _save_good_tag(tmpdir, cfg)
    os.remove(os.path.join(save_dir, "one", MANIFEST_NAME))

    engine2 = make_simple_engine(tmpdir, cfg, seed=99)
    name, _ = engine2.load_checkpoint(save_dir)
    assert "one" in name
    assert engine2.global_steps == steps_one
    _tree_equal(engine2.params, params_one)


# ---------------------------------------------------------------------------
# Tag watch: latest_committed_tag + TagWatcher (the rollout controller's
# view of the commit protocol — no engine needed, pure manifest-level).
# ---------------------------------------------------------------------------

from deepspeed_tpu.runtime.checkpoint import (  # noqa: E402
    CheckpointStorage,
    TagWatcher,
    latest_committed_tag,
)


def _commit_plain_tag(root, tag, payload=b"w"):
    w = CheckpointStorage().tag_writer(str(root), tag)
    w.write_file("weights.bin", payload)
    w.commit()


def test_latest_committed_tag_orders_by_sequence(tmpdir):
    root = str(tmpdir.join("ckpt"))
    assert latest_committed_tag(root) is None          # absent root
    _commit_plain_tag(root, "zz-first")
    _commit_plain_tag(root, "aa-second")               # lexically earlier
    assert latest_committed_tag(root) == ("aa-second", 2)  # sequence wins


def test_latest_committed_tag_ignores_torn_and_uncommitted(tmpdir):
    root = str(tmpdir.join("ckpt"))
    _commit_plain_tag(root, "good")
    # an uncommitted tag dir (crash before the manifest landed)
    os.makedirs(os.path.join(root, "torn"))
    with open(os.path.join(root, "torn", "weights.bin"), "wb") as f:
        f.write(b"partial")
    # a torn manifest (crash mid-write): unparseable = uncommitted
    os.makedirs(os.path.join(root, "half"))
    with open(os.path.join(root, "half", MANIFEST_NAME), "w") as f:
        f.write('{"version": 1, "seq')
    # a stray file at the root is not a tag
    with open(os.path.join(root, "latest"), "w") as f:
        f.write("half")
    assert latest_committed_tag(root) == ("good", 1)


def test_tag_watcher_reports_each_change_once(tmpdir):
    root = str(tmpdir.join("ckpt"))
    w = TagWatcher(root)                   # over a not-yet-created root
    assert w.current() is None and w.poll() is None
    _commit_plain_tag(root, "a")
    assert w.poll() == ("a", 1)
    assert w.poll() is None                # no change, no report
    _commit_plain_tag(root, "b")
    _commit_plain_tag(root, "c")           # two commits between polls:
    assert w.poll() == ("c", 3)            # only the latest is reported
    assert w.poll() is None


def test_tag_watcher_reports_rollback_to_previous_tag(tmpdir):
    root = str(tmpdir.join("ckpt"))
    _commit_plain_tag(root, "a")
    _commit_plain_tag(root, "b")
    w = TagWatcher(root)                   # starts at ("b", 2)
    assert w.poll() is None
    # operator rollback: deleting the newest manifest regresses latest
    os.remove(os.path.join(root, "b", MANIFEST_NAME))
    assert w.poll() == ("a", 1)
    assert w.poll() is None
    # ...and rolling everything out reports None-as-change exactly once
    os.remove(os.path.join(root, "a", MANIFEST_NAME))
    assert w.current() is None
