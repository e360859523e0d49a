"""Compiler-driven train-step fusion: overlapped per-bucket backward/reduce,
donated buffers, and the interleaved-1F1B pipeline schedule.

Five proof layers, mirroring the bench leg (TRAIN_BENCH_CPU.json):

- ``compute_bucket_ranges`` round-trips every leaf exactly once under any
  bucket size (the overlap tap's bucket plan).
- The overlapped fused step is a BITWISE no-op vs the sequential step for
  ZeRO stages 1 and 2 — the tap is the identity; only reduce *placement*
  moves.
- Donation pins: params/opt_state/scaler alias their outputs in the
  compiled HLO, the stacked microbatch buffers become ``buffer_donor``
  only under overlap_comm, a CompileSentinel sees exactly one compile
  across repeated steps, and donated param buffers are really gone
  (no post-donation reads).
- Gradients leave the backward pass in their parameter's layout: the
  compiled ZeRO-2 step of a tiny BERT holds no collective in the chunked
  loss's loops (for four described v5e chips; over CPU devices the carry
  is whole), and ZeRO 1/2/3 and ZeRO-2 x tensor parallel match a
  one-device engine, fused and with two microbatches.
- The interleaved schedule's instruction streams match hand-computed
  Megatron-style traces at (S=2, V=2) and (S=4, V=2), and the dataflow
  simulator reproduces the analytic bubble ideals exactly.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.pipe import schedule as ps
from deepspeed_tpu.runtime.pipe.compiled import analytic_bubble_fraction
from deepspeed_tpu.runtime.zero.sharded_optimizer import compute_bucket_ranges
from deepspeed_tpu.profiling.sentinels import CompileSentinel

from tests.unit.simple_model import create_simple_model

HIDDEN = 16


# ---------------------------------------------------------------------------
# bucket plan
# ---------------------------------------------------------------------------

class TestComputeBucketRanges:
    def test_round_trip_covers_every_leaf_once(self):
        sizes = [5, 10, 3, 8, 1, 7, 2]
        for bucket_size in (1, 4, 10, 15, 36, 1000):
            ranges = compute_bucket_ranges(sizes, bucket_size)
            # contiguous, in order, half-open, covering [0, len) exactly
            assert ranges[0][0] == 0
            assert ranges[-1][1] == len(sizes)
            for (lo, hi), (lo2, _) in zip(ranges, ranges[1:]):
                assert hi == lo2
                assert lo < hi

    def test_respects_bucket_size_cap(self):
        sizes = [4, 4, 4, 4]
        ranges = compute_bucket_ranges(sizes, 8)
        assert ranges == [(0, 2), (2, 4)]
        for lo, hi in ranges:
            assert sum(sizes[lo:hi]) <= 8

    def test_oversized_leaf_gets_own_bucket(self):
        sizes = [2, 100, 2]
        ranges = compute_bucket_ranges(sizes, 10)
        assert (1, 2) in ranges  # the 100-element leaf alone
        assert ranges[0] == (0, 1) and ranges[-1] == (2, 3)

    def test_huge_bucket_is_monolithic(self):
        assert compute_bucket_ranges([3, 3, 3], 1 << 60) == [(0, 3)]

    def test_degenerate_bucket_size_clamps(self):
        # size <= 0 clamps to 1 element -> one leaf per bucket
        assert compute_bucket_ranges([5, 5], 0) == [(0, 1), (1, 2)]


# ---------------------------------------------------------------------------
# overlapped vs sequential: the tap must be bitwise-invisible
# ---------------------------------------------------------------------------

def _make_engine(stage, overlap, bucket=96, sentinels=False, seed=5):
    model, params = create_simple_model(hidden_dim=HIDDEN, seed=seed)
    config = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, "overlap_comm": overlap,
                              "reduce_bucket_size": bucket},
    }
    if sentinels:
        config["jax_sentinels"] = {"enabled": True, "compile_budget": 2}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(16, HIDDEN).astype(np.float32),
             rng.randn(16, HIDDEN).astype(np.float32)) for _ in range(n)]


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


class TestOverlapParity:
    @pytest.mark.parametrize("stage", [1, 2])
    def test_bitwise_parity_and_bucket_plan(self, stage):
        data = _batches(3)
        seq = _make_engine(stage, overlap=False)
        ovl = _make_engine(stage, overlap=True)
        seq_losses = [float(jax.device_get(seq.train_step([b]))) for b in data]
        ovl_losses = [float(jax.device_get(ovl.train_step([b]))) for b in data]
        assert seq_losses == ovl_losses  # bitwise: float() of the same fp32
        for a, b in zip(_leaves(seq.params), _leaves(ovl.params)):
            np.testing.assert_array_equal(a, b)
        # the plan actually split the leaves (SimpleModel: 4 leaves, 544 elems)
        assert len(ovl.optimizer.bucket_numels) >= 2
        assert seq.optimizer._buckets is None  # overlap off: no plan built

    def test_learning_happens(self):
        eng = _make_engine(2, overlap=True)
        data = _batches(6, seed=3)
        losses = [float(jax.device_get(eng.train_step([b]))) for b in data[:1] * 6]
        assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# donation pins
# ---------------------------------------------------------------------------

def _compiled_text(engine, *stacked, mesh=None):
    """The compiled fused-step HLO for ``stacked`` ([gas, batch, ...] each);
    ``mesh`` names devices that are described and not attached (a TPU
    topology laid out as the engine's mesh) to compile for instead."""
    engine._ensure_opt_state()
    args = (engine.params, engine.opt_state, engine.scaler_state,
            jax.random.PRNGKey(0), jnp.float32(1.0), jnp.float32(1e-3),
            *stacked)
    if mesh is not None:
        # the engine's programs read the mesh when they are traced
        engine.mesh = engine.optimizer.mesh = mesh
        args = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(
                mesh, getattr(x.sharding, "spec", PartitionSpec()))), args)
    fused = engine._get_train_step(engine._module_needs_rng(), len(stacked))
    traced = getattr(fused, "_fn", fused).trace(*args)
    lowered = (traced.lower() if mesh is None
               else traced.lower(lowering_platforms=("tpu",)))
    return lowered.compile().as_text()


def _compiled_head(engine):
    """First line of the compiled fused-step HLO (module attrs incl. aliasing)."""
    x = jnp.zeros((1, 16, HIDDEN), jnp.float32)
    return _compiled_text(engine, x, x).split("\n", 1)[0]


class TestDonationPins:
    def test_state_aliases_and_batch_donation_only_under_overlap(self):
        head_seq = _compiled_head(_make_engine(2, overlap=False))
        head_ovl = _compiled_head(_make_engine(2, overlap=True))
        # params/opt_state/scaler alias outputs in both programs
        for head in (head_seq, head_ovl):
            assert "input_output_alias=" in head
        # the stacked microbatch buffers are donor-only (no aliased output:
        # they die inside the program) and ONLY under overlap_comm — the
        # 3-call/test paths may re-feed a batch object across calls
        assert "buffer_donor=" in head_ovl
        assert "buffer_donor=" not in head_seq

    def test_no_recompiles_across_steps_and_no_post_donation_reads(self):
        eng = _make_engine(2, overlap=True, sentinels=True)
        data = _batches(3, seed=9)
        eng.train_step([data[0]])
        fused = eng._get_train_step(eng._module_needs_rng(), 2)
        assert isinstance(fused, CompileSentinel)
        p_old = jax.tree_util.tree_leaves(eng.params)
        for b in data[1:]:
            eng.train_step([b])
        # one program, compiled once, across distinct batches
        assert fused.check() == 1
        # donated: the pre-step param buffers must be gone, and reading
        # them must raise instead of silently returning stale memory
        assert all(x.is_deleted() for x in p_old)
        with pytest.raises(RuntimeError):
            np.asarray(p_old[0])


# ---------------------------------------------------------------------------
# the loss under a sharded batch
# ---------------------------------------------------------------------------

def _tiny_bert_zero2(vocab_size, hidden, layers, seq=64, dp=4, **config):
    """(engine, stacked batch) of a tiny BertForPreTraining under ZeRO-2
    over 4 devices: 32 x 64 = 2048 rows, 4 chunks of the loss's 512
    (``dp=1``: the same batch on one device, no ZeRO)."""
    from deepspeed_tpu.models.bert import BertConfig, init_bert

    B, S = 32, seq
    model, params = init_bert(BertConfig(
        vocab_size=vocab_size, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=4, intermediate_size=2 * hidden,
        max_position_embeddings=S, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0), batch_size=2, seq_len=S)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": B, "train_micro_batch_size_per_gpu": B // dp,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2 if dp > 1 else 0},
            "mesh": {"data_parallel_size": dp}, **config})
    ids = np.zeros((B, S), np.int32)
    return engine, [engine._shard_stacked(jnp.asarray(x)[None]) for x in
                    (ids, ids, ids + 1, ids, np.zeros((B,), np.int32))]


def test_fused_zero2_step_gathers_no_hidden_states_for_the_loss():
    """A tiny BertForPreTraining under ZeRO-2 over 4 devices: the compiled
    fused step all-gathers nothing shaped like the chunked loss's
    [n_chunks, rows, H] array (the loss's scan once walked the axis the batch
    sharding splits, and every device gathered every row, forward and
    backward)."""
    H = 48
    engine, stacked = _tiny_bert_zero2(256, H, 1)
    text = _compiled_text(engine, *stacked)
    chunks = f"[4,512,{H}]"
    gathers = [l.strip() for l in text.splitlines()
               if re.search(r"= \S+ all-gather(-start)?\(", l)]
    assert gathers, "ZeRO-2 over 4 devices gathers at least its parameters"
    assert not [l for l in gathers if chunks in l.split(" all-gather")[0]]


# ---------------------------------------------------------------------------
# gradients leave the backward pass in their parameter's layout
# ---------------------------------------------------------------------------

_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_COLLECTIVE = re.compile(
    r"\s(all-reduce|reduce-scatter|all-reduce-scatter|collective-permute"
    r"|all-gather|all-to-all)(-start)?\(")


def _computations(text):
    """HLO text, compiled or as traced (whose computations are headed by
    their name alone), as {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*->.*)?\{\s*$",
                        line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line.strip())
    return comps


def _loop_bodies(text):
    """For each while of a compiled HLO text, every line its body reaches:
    the body's own and those of the fusions, reducers and nested loops it
    calls."""
    comps = _computations(text)
    out = []
    for lines in comps.values():
        for line in lines:
            body = re.search(r"\swhile\(.*body=%?([\w.\-]+)", line)
            if not body:
                continue
            seen, todo = [], [body.group(1)]
            while todo:
                name = todo.pop()
                if name in seen or name not in comps:
                    continue
                seen.append(name)
                for inner in comps[name]:
                    for m in _CALLED.finditer(inner):
                        todo.extend(n.strip().lstrip("%") for n in
                                    (m.group(1) or m.group(2)).split(","))
            out.append([l for name in seen for l in comps[name]])
    return out


def _collectives(lines):
    """The collectives among ``lines``, by opcode; the TPU compiler's
    all-reduce-scatter is a fusion that calls a computation of that name."""
    heads = [l.split(", metadata=")[0] for l in lines]
    return [h for h in heads if _COLLECTIVE.search(h)
            or (" fusion(" in h and "all-reduce-scatter" in h)]


def _loss_loops(text):
    """Bodies of the whiles that belong to the chunked loss, forward and
    backward: their operations are named ``.../BertForPreTraining/while/
    body/...``, the encoder's ``.../bert/encoder/while/body/...``."""
    return [lines for lines in _loop_bodies(text)
            if any("BertForPreTraining/while/body" in l for l in lines)]


def _tiny_bert_zero2_step(mesh_of=None):
    """The compiled fused ZeRO-2 step of a tiny bf16 BertForPreTraining whose
    vocabulary (250) no 4 chips split evenly; ``mesh_of(cpu_mesh)`` names
    described devices to compile for."""
    engine, stacked = _tiny_bert_zero2(250, 64, 2, bf16={"enabled": True})
    return _compiled_text(
        engine, *stacked, mesh=mesh_of and mesh_of(engine.mesh))


@pytest.fixture()
def v5e_mesh_of():
    """``mesh_of(cpu_mesh)``: the same mesh over four described v5e chips
    (described, not attached: the installed TPU compiler compiles for them
    and nothing runs)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep it out of one
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda cpu: Mesh(
        np.array(topo.devices[:cpu.devices.size]).reshape(cpu.devices.shape),
        cpu.axis_names)
    jax.config.update("jax_enable_compilation_cache", cache_was)


def test_zero2_flat_shard_stays_out_of_the_loss_loops_on_described_v5e(v5e_mesh_of):
    """Compiled for four described v5e chips, neither loop of the chunked
    loss holds a collective: each chunk's partial kernel gradient is summed
    locally and reduced once after the loop. Before the gradients were
    pinned to their parameters' layout, ZeRO-2's flat ``P('data')`` shard
    reached back into the backward loop's carry, split the word table's
    gradient over the vocabulary, and the loop reduce-scattered it (with a
    halo exchange, 250 / 4 being uneven) once a chunk."""
    text = _tiny_bert_zero2_step(v5e_mesh_of)
    loops = _loss_loops(text)
    assert len(loops) == 2, "the loss's forward and backward scans"
    assert [_collectives(lines) for lines in loops] == [[], []]
    # the instrument sees a collective in a loop when there is one: the
    # encoder's backward loop reduces its layer's gradients
    assert any(_collectives(lines) for lines in _loop_bodies(text))


def test_zero2_parameter_gather_is_one_bf16_all_gather_on_described_v5e(v5e_mesh_of):
    """Compiled for four described v5e chips, ZeRO-2's step rebuilds the
    parameters with an all-gather of the bf16 shards of the padded flat
    vector. A shard that is not whole 128-lane tiles (the flat vector padded
    to ``dp`` alone: this model's numel is no multiple of 512) the TPU
    compiler instead copies into a zero-filled vector of the whole length
    and all-reduces: twice the bytes, and once the largest operation of
    BERT-large's four-chip step."""
    engine, stacked = _tiny_bert_zero2(250, 64, 2, bf16={"enabled": True})
    text = _compiled_text(engine, *stacked, mesh=v5e_mesh_of(engine.mesh))
    numel, padded, dp = (engine.optimizer._numel, engine.optimizer._padded,
                         engine.optimizer.dp)
    assert numel % (dp * 128), "the model must need the padding"
    kinds = _collective_kinds(text)
    whole = (f"[{padded}]", f"[{dp},1,{padded // dp}]")
    gathers = [result for result, opcode, _ in kinds
               if opcode == "all-gather" and any(w in result for w in whole)]
    assert len(gathers) == 1 and gathers[0].startswith("bf16["), kinds
    # nothing of the flat vector's length is all-reduced, at the new padding
    # or at the old (numel rounded up to dp)
    flat = {f"[{n}]" for n in (numel, -(-numel // dp) * dp, padded)}
    assert not [k for k in kinds if k[1] == "all-reduce"
                and any(n in k[0] for n in flat)], kinds


def _collective_kinds(text):
    """Every collective of a compiled HLO text as (result, opcode, replica
    groups), sorted: what the program sends, whatever its operands are
    called."""
    found = []
    for head in _collectives(text.splitlines()):
        op = _COLLECTIVE.search(head)
        opcode = op.group(1) if op else "fusion"     # an all-reduce-scatter
        result = head.partition(" = ")[2].partition(f" {opcode}")[0]
        groups = re.search(r"replica_groups=([^ ]+?),? ", head + " ")
        found.append((result, opcode, groups and groups.group(1)))
    return sorted(found, key=str)


@pytest.mark.parametrize("chips", [1, 4])
def test_bert_step_at_seq_128_runs_the_materialised_attention(
        monkeypatch, v5e_mesh_of, chips):
    """BERT's fused step at seq 128 (dropout 0, as the benchmark's cells run
    it), compiled for described v5e chips: the rule sends the attention to
    the materialised path, so the program holds no Mosaic call, and over
    four chips it sends what the step with the kernels sends and nothing
    else (no gather of q, k, v or of the scores: plain einsums over a
    batch-sharded q need no shard_map)."""
    from deepspeed_tpu.ops.transformer import attention as attn

    def step(**patches):
        # the model is initialised here, on the CPU; only the step's trace
        # takes the TPU's branch
        engine, stacked = _tiny_bert_zero2(
            256, 64, 2, seq=128, dp=chips, bf16={"enabled": True},
            activation_checkpointing={"enabled": True})
        with monkeypatch.context() as on_tpu:
            on_tpu.setattr(attn, "_on_tpu", lambda: True)
            for name, value in patches.items():
                on_tpu.setattr(attn, name, value)
            before = attn.trace_counts()
            text = _compiled_text(engine, *stacked,
                                  mesh=v5e_mesh_of(engine.mesh))
            return text, attn.traced_implementation(since=before)

    text, traced = step()
    assert traced == "dense"
    assert "tpu_custom_call" not in text
    # the same step with the rule switched off, as the parent ran it
    kernels, traced = step(materialises_scores=lambda *a, **k: False)
    assert traced == "pallas"
    assert kernels.count("tpu_custom_call") >= 3    # the instrument sees one
    assert _collective_kinds(text) == _collective_kinds(kernels)
    assert bool(_collective_kinds(text)) == (chips > 1)


def test_zero2_flat_shard_does_not_split_the_loss_loops_carry_on_cpu():
    """The CPU compiler does not move an all-reduce out of a loop, so over 4
    CPU devices the backward loop of the loss still reduces each chunk's
    partial kernel gradient; what the pinned layout changes there is the
    carry: whole and summed over all four devices, where ZeRO-2's flat shard
    had split it over the vocabulary and reduced it in groups of two."""
    loops = _loss_loops(_tiny_bert_zero2_step())
    assert len(loops) == 2
    found = [c for lines in loops for c in _collectives(lines)]
    assert found and all(" all-reduce(" in c for c in found), found
    assert all("replica_groups=[1,4]<=[4]" in c for c in found), found
    assert any("[250,64]" in c.split(" all-reduce(")[0] for c in found), found


def _layout_case(case, gas=1, batch=16):
    """(engine, x, y) of test_zero_tp's two-layer MLP under ``case``: a ZeRO
    stage over 4 devices, ZeRO-2 over a 2 x 2 (data, model) mesh, or the
    one-device stage-0 engine the others are compared with."""
    from tests.unit.test_zero_tp import make_model_and_batch

    stage, dp, tp = {"one_device": (0, 1, 1), "zero1": (1, 4, 1),
                     "zero2": (2, 4, 1), "zero3": (3, 4, 1),
                     "zero2_tp2": (2, 2, 2)}[case]
    model, params, x, y = make_model_and_batch()
    config = {
        "train_batch_size": batch * gas,
        "train_micro_batch_size_per_gpu": batch // dp,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"data_parallel_size": dp}}
    if stage:
        config["zero_optimization"] = {"stage": stage}
    if tp > 1:
        config["tensor_parallel"] = {"size": tp}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine, np.asarray(x), np.asarray(y)


def _fwd_bwd(engine, x, y):
    return engine._get_fwd_bwd(False)(
        engine.params, jnp.float32(1.0), jax.random.PRNGKey(0),
        jnp.float32(1.0), x, y)


LAYOUT_CASES = ["zero1", "zero2", "zero3", "zero2_tp2"]


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_gradients_leave_in_their_parameters_layout(case):
    """Each gradient leaf comes out of the backward pass laid out as its
    parameter is stored (replicated under ZeRO-1/2, split over ``data`` as
    stored under ZeRO-3, over ``model`` under tensor parallelism) and equal
    to the one-device gradient."""
    engine, x, y = _layout_case(case)
    base, _, _ = _layout_case("one_device")
    _, want = _fwd_bwd(base, x, y)
    _, grads = _fwd_bwd(engine, x, y)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, g), (_, p), (_, w) in zip(
            flat(grads), flat(engine.params), flat(want)):
        assert g.sharding.is_equivalent_to(p.sharding, g.ndim), (path, g.sharding)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-7)
    ff1 = tuple(grads["params"]["ff1"]["kernel"].sharding.spec)
    assert ff1 + (None,) * (2 - len(ff1)) == {
        "zero3": ("data", None), "zero2_tp2": (None, "model")
    }.get(case, (None, None))


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_fused_step_matches_the_one_device_step(case):
    engine, x, y = _layout_case(case)
    base, _, _ = _layout_case("one_device")
    losses = [float(e.train_step([(x, y)])) for e in (base, engine)]
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-4)
    for a, b in zip(_leaves(base.params), _leaves(engine.params)):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_two_microbatches_give_the_doubled_batchs_update(case):
    rng = np.random.RandomState(1)
    _, x, y = _layout_case("one_device")
    x2, y2 = (rng.randn(*a.shape).astype(np.float32) for a in (x, y))
    two, _, _ = _layout_case(case, gas=2)
    one, _, _ = _layout_case(case, batch=32)
    loss_two = float(two.train_step([(x, y), (x2, y2)]))
    loss_one = float(one.train_step(
        [(np.concatenate([x, x2]), np.concatenate([y, y2]))]))
    np.testing.assert_allclose(loss_two, loss_one, rtol=1e-5)
    for a, b in zip(_leaves(one.params), _leaves(two.params)):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# ZeRO's parameter gather: whole lane tiles a rank, cast before it travels
# ---------------------------------------------------------------------------

def _odd_engine(stage, dp, precision):
    """An engine over a two-layer MLP of 63 parameters (an odd numel) whose
    loss counts only rows with a nonzero input, fed batches with ONE such
    row: every sum over rows or devices then adds zeros to one term, so no
    reduction order rounds and engines over 1 and 4 devices see the same
    gradient bits."""
    import flax.linen as nn

    class OddMLP(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            h = nn.relu(nn.Dense(7, name="a")(x))
            err = (nn.Dense(3, use_bias=False, name="b")(h) - y) ** 2
            live = jnp.abs(x).sum(-1, keepdims=True) > 0
            return jnp.sum(err * live) / 16

    zeros = np.zeros((16, 5), np.float32), np.zeros((16, 3), np.float32)
    model = OddMLP()
    params = model.init(jax.random.PRNGKey(0), *zeros)
    config = {
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 16 // dp,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "mesh": {"data_parallel_size": dp}}
    if stage:
        config["zero_optimization"] = {"stage": stage}
    if precision == "bf16":
        config["bf16"] = {"enabled": True}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    rng = np.random.RandomState(0)
    for step in range(3):
        x, y = (a.copy() for a in zeros)
        row = (5 * step) % 16       # a row of device 0, then 1, then 2
        x[row], y[row] = rng.randn(5), rng.randn(3)
        engine.train_step([(x, y)])
    return engine


@pytest.mark.parametrize("precision", ["bf16", "fp32"])
@pytest.mark.parametrize("stage", [1, 2])
def test_zero_steps_over_an_odd_numel_give_the_unsharded_parameters(
        stage, precision):
    """ZeRO-1/2 over four devices, the flat vector of an odd numel padded to
    whole lane tiles a rank and (bf16) the shard cast before it is gathered:
    after three steps the parameters are, bit for bit, those of the engine
    without ZeRO over the same four devices and, under bf16, over one
    device. (fp32 compute over ONE device is held to 1e-6 only: XLA's CPU
    matmul rounds a 16-row operand's products otherwise than a 4-row one's,
    stage 0 included.)"""
    engine = _odd_engine(stage, 4, precision)
    opt = engine.optimizer
    assert (opt._numel, opt._padded) == (63, 512)
    as_stored = jnp.bfloat16 if precision == "bf16" else jnp.float32
    for dp in (4, 1):
        base = _odd_engine(0, dp, precision)
        for got, want in zip(_leaves(engine.params), _leaves(
                jax.tree_util.tree_map(lambda p: p.astype(as_stored),
                                       base.params))):
            assert got.dtype == want.dtype
            if precision == "fp32" and dp == 1:
                np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
            else:
                np.testing.assert_array_equal(got, want)
    # zeros behind numel, in the master and in both moments
    state = jax.device_get(engine.opt_state)
    flats = [x for x in jax.tree_util.tree_leaves(state)
             if getattr(x, "shape", ()) == (512,)]
    assert len(flats) == (3 if precision == "bf16" else 2)
    assert not any(np.asarray(x)[63:].any() for x in flats)
    if precision == "bf16":
        # gathered-then-cast is cast-then-gathered: each parameter is its
        # slice of the float32 master, rounded
        want = np.asarray(state.flat_master[:63].astype(jnp.bfloat16))
        got = np.concatenate([x.reshape(-1) for x in _leaves(engine.params)])
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("stage,precision,itemsize", [
    (2, "bf16", 2), (1, "bf16", 2), (2, "fp32", 4)])
def test_param_gather_gauges_read_what_the_rule_predicts(
        stage, precision, itemsize):
    """Tracing ``update()`` records the bytes a rank receives in the
    parameter gather, the other ranks' shards of the padded vector in the
    dtype that travels, and that dtype's size."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.runtime.zero import sharded_optimizer as so

    gauges = telemetry.get_registry()
    for name in (so.PARAM_GATHER_BYTES, so.PARAM_GATHER_ITEMSIZE):
        gauges.gauge(name).set(-1)
    engine = _odd_engine(stage, 4, precision)
    assert so.flat_pad_multiple(4) == 512
    assert engine.optimizer._padded == 512
    assert gauges.gauge(so.PARAM_GATHER_ITEMSIZE).value == itemsize
    assert gauges.gauge(so.PARAM_GATHER_BYTES).value == 3 * 128 * itemsize


# ---------------------------------------------------------------------------
# interleaved schedule: hand-computed traces
# ---------------------------------------------------------------------------

def _fb_stream(sched):
    """[(F|B, chunk, mb), ...] in dispatch order; mb recovered per (kind,
    chunk) counter exactly as the engine and simulator do."""
    ops, counts = [], {}
    for tick in sched.steps():
        for cmd in tick:
            if isinstance(cmd, (ps.ForwardPass, ps.BackwardPass)):
                kind = "F" if isinstance(cmd, ps.ForwardPass) else "B"
                mb = counts.get((kind, cmd.chunk_id), 0)
                counts[(kind, cmd.chunk_id)] = mb + 1
                ops.append((kind, cmd.chunk_id, mb))
    return ops


class TestInterleavedScheduleOrder:
    def test_s2_v2_rank0_trace(self):
        sched = ps.InterleavedTrainSchedule(
            micro_batches=2, stages=2, stage_id=0, num_model_chunks=2)
        # warmup = min(M*V, 2*(S-1) + (V-1)*S) = 4 = all forwards first;
        # forwards walk chunk 0 for a group of S microbatches, then chunk 1;
        # backwards walk chunks in reverse
        assert _fb_stream(sched) == [
            ("F", 0, 0), ("F", 0, 1), ("F", 1, 0), ("F", 1, 1),
            ("B", 1, 0), ("B", 1, 1), ("B", 0, 0), ("B", 0, 1),
        ]

    def test_s2_v2_rank1_trace(self):
        sched = ps.InterleavedTrainSchedule(
            micro_batches=2, stages=2, stage_id=1, num_model_chunks=2)
        # warmup = min(4, 0 + S) = 2, then steady 1F1B, then drain
        assert _fb_stream(sched) == [
            ("F", 0, 0), ("F", 0, 1),
            ("F", 1, 0), ("B", 1, 0), ("F", 1, 1), ("B", 1, 1),
            ("B", 0, 0), ("B", 0, 1),
        ]

    def test_s4_v2_rank0_trace(self):
        sched = ps.InterleavedTrainSchedule(
            micro_batches=4, stages=4, stage_id=0, num_model_chunks=2)
        # warmup = min(8, 2*3 + 4) = 8: every forward before any backward
        assert _fb_stream(sched) == (
            [("F", 0, m) for m in range(4)] + [("F", 1, m) for m in range(4)]
            + [("B", 1, m) for m in range(4)] + [("B", 0, m) for m in range(4)]
        )

    def test_s4_v2_rank3_trace(self):
        sched = ps.InterleavedTrainSchedule(
            micro_batches=4, stages=4, stage_id=3, num_model_chunks=2)
        # last rank: warmup = (V-1)*S = 4, steady alternation on chunk 1,
        # then the chunk-0 backward drain
        assert _fb_stream(sched) == [
            ("F", 0, 0), ("F", 0, 1), ("F", 0, 2), ("F", 0, 3),
            ("F", 1, 0), ("B", 1, 0), ("F", 1, 1), ("B", 1, 1),
            ("F", 1, 2), ("B", 1, 2), ("F", 1, 3), ("B", 1, 3),
            ("B", 0, 0), ("B", 0, 1), ("B", 0, 2), ("B", 0, 3),
        ]

    def test_buffer_op_structure_and_chunk_ids(self):
        # rank 0 of (S=2, V=2): chunk 0 is virtual stage 0 (Load + Forward +
        # Send), chunk 1 is virtual stage 2 (Recv + Forward + Send); backward
        # mirrors with grads, and virtual stage 0 never sends grads
        sched = ps.InterleavedTrainSchedule(
            micro_batches=2, stages=2, stage_id=0, num_model_chunks=2)
        ticks = [t for t in sched.steps() if t]
        fwd_c0, fwd_c1 = ticks[0], ticks[2]
        assert [type(c) for c in fwd_c0] == [
            ps.LoadMicroBatch, ps.ForwardPass, ps.SendActivation]
        assert [type(c) for c in fwd_c1] == [
            ps.RecvActivation, ps.ForwardPass, ps.SendActivation]
        assert all(c.chunk_id == 0 for c in fwd_c0)
        assert all(c.chunk_id == 1 for c in fwd_c1)
        bwd_c1, bwd_c0 = ticks[4], ticks[6]
        assert [type(c) for c in bwd_c1] == [
            ps.RecvGrad, ps.BackwardPass, ps.SendGrad]
        assert [type(c) for c in bwd_c0] == [ps.RecvGrad, ps.BackwardPass]

    def test_last_virtual_stage_loads_labels(self):
        # rank 1 of (S=2, V=2): chunk 1 is the LAST virtual stage — it loads
        # the microbatch (labels) in addition to receiving activations
        sched = ps.InterleavedTrainSchedule(
            micro_batches=2, stages=2, stage_id=1, num_model_chunks=2)
        loads = [c for t in sched.steps() for c in t
                 if isinstance(c, ps.LoadMicroBatch)]
        assert loads and all(c.chunk_id == 1 for c in loads)

    def test_idle_prefix_matches_rank(self):
        for r in range(4):
            sched = ps.InterleavedTrainSchedule(
                micro_batches=4, stages=4, stage_id=r, num_model_chunks=2)
            ticks = list(sched.steps())
            assert ticks[:r] == [[]] * r
            if r:
                assert ticks[r] != []

    def test_tail_reduces_and_steps_every_chunk(self):
        sched = ps.InterleavedTrainSchedule(
            micro_batches=4, stages=4, stage_id=1, num_model_chunks=2)
        tail = list(sched.steps())[-1]
        assert [(type(c), c.chunk_id) for c in tail] == [
            (ps.ReduceTiedGrads, 0), (ps.ReduceGrads, 0), (ps.OptimizerStep, 0),
            (ps.ReduceTiedGrads, 1), (ps.ReduceGrads, 1), (ps.OptimizerStep, 1),
        ]

    def test_divisibility_is_enforced(self):
        with pytest.raises(ValueError, match="divisible"):
            ps.InterleavedTrainSchedule(
                micro_batches=3, stages=2, stage_id=0, num_model_chunks=2)


# ---------------------------------------------------------------------------
# bubble simulator vs analytic ideals
# ---------------------------------------------------------------------------

class TestBubbleFractions:
    @pytest.mark.parametrize("S,M,V", [
        (4, 8, 1), (4, 8, 2), (2, 4, 1), (2, 4, 2),
        (2, 2, 2), (4, 4, 2), (8, 8, 1),
    ])
    def test_simulator_reproduces_analytic(self, S, M, V):
        sim = ps.simulate_bubble_fraction(S, M, num_model_chunks=V)
        assert sim == pytest.approx(
            analytic_bubble_fraction(S, M, num_model_chunks=V), abs=1e-9)

    def test_interleaving_strictly_shrinks_the_bubble(self):
        for S, M in [(4, 8), (2, 4), (4, 4)]:
            b1 = ps.simulate_bubble_fraction(S, M, num_model_chunks=1)
            b2 = ps.simulate_bubble_fraction(S, M, num_model_chunks=2)
            assert b2 < b1

    def test_gated_pair_values(self):
        # the exact S=4, M=8 pair TRAIN_BENCH_CPU.json commits and the
        # bench gate refuses to regress: 0.2727 -> 0.1579
        assert ps.simulate_bubble_fraction(4, 8) == pytest.approx(3 / 11)
        assert ps.simulate_bubble_fraction(
            4, 8, num_model_chunks=2) == pytest.approx(3 / 19)


# ---------------------------------------------------------------------------
# config validation: named errors
# ---------------------------------------------------------------------------

def _cfg(**over):
    base = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    base.update(over)
    return base


class TestFusionConfigValidation:
    def test_nonpositive_bucket_size_is_named(self):
        with pytest.raises(DeepSpeedConfigError, match="reduce_bucket_size"):
            DeepSpeedConfig(_cfg(zero_optimization={
                "stage": 2, "reduce_bucket_size": 0}), world_size=8)

    def test_non_bool_overlap_comm_is_named(self):
        with pytest.raises(DeepSpeedConfigError, match="overlap_comm"):
            DeepSpeedConfig(_cfg(zero_optimization={
                "stage": 2, "overlap_comm": "yes"}), world_size=8)

    def test_bad_num_model_chunks_is_named(self):
        with pytest.raises(DeepSpeedConfigError, match="num_model_chunks"):
            DeepSpeedConfig(_cfg(pipeline={"num_model_chunks": 0}),
                            world_size=8)

    def test_interleave_divisibility_is_named(self):
        with pytest.raises(DeepSpeedConfigError, match="divisible"):
            DeepSpeedConfig(_cfg(
                gradient_accumulation_steps=3,
                train_batch_size=48,
                pipeline={"stages": 2, "num_model_chunks": 2}), world_size=8)

    def test_valid_fusion_config_accepted(self):
        cfg = DeepSpeedConfig(_cfg(
            zero_optimization={"stage": 2, "overlap_comm": True,
                               "reduce_bucket_size": 4096},
            gradient_accumulation_steps=4,
            train_batch_size=64,
            pipeline={"stages": 2, "num_model_chunks": 2}), world_size=8)
        assert cfg.zero_config.overlap_comm is True
