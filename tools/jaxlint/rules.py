"""jaxlint rule registry: every hazard the analyzer knows, by code.

Each rule is a static JAX-hazard class with a stable ``JLxxx`` code used
in findings, inline suppressions (``# jaxlint: disable=JL002(reason)``),
and the checked-in baseline. The detection logic lives in analyzer.py
(JL001-JL006, per-function) and the rules_*.py modules (JL007-JL011,
interprocedural over the pass-1 call graph); this module is the single
place codes, names, rationales, and ``--explain`` material are defined
(docs/static_analysis.md documents each with examples).
"""

from dataclasses import dataclass


@dataclass(frozen=True)
class Rule:
    code: str
    name: str
    summary: str
    doc: str = ""        # longer prose for --explain
    example: str = ""    # minimal repro snippet for --explain


RULES = {
    "JL001": Rule(
        "JL001", "traced-python-branch",
        "Python if/while/assert on a traced argument inside a jitted "
        "function: concretization error at trace time, or a silent "
        "recompile per value if the arg is marked static later.",
        doc="Inside jit, python control flow runs at trace time against "
            "abstract tracers; branching on a traced value either raises a "
            "ConcretizationTypeError or, if the argument is later marked "
            "static, recompiles once per distinct value. Use jnp.where / "
            "lax.cond, or genuinely static arguments.",
        example=(
            "@jax.jit\n"
            "def f(x, flag):\n"
            "    if flag:          # JL001: traced python branch\n"
            "        return x * 2\n"
            "    return x"
        )),
    "JL002": Rule(
        "JL002", "host-sync-in-hot-loop",
        "Host-synchronizing call (.item(), float()/int()/bool() on device "
        "values, np.asarray, jax.device_get, block_until_ready) inside a "
        "registered hot-loop function: stalls the device pipeline every "
        "iteration.",
        doc="Registered hot loops (rules.HOT_LOOPS, or a '# jaxlint: hot' "
            "marker) are the per-step code the async dispatch queue must "
            "keep fed. Any device->host materialization inside them drains "
            "the queue and serializes the step. Hoist the sync out of the "
            "loop or batch it to one transfer per interval.",
        example=(
            "def train_step(self, batch):   # jaxlint: hot\n"
            "    loss = self._step(batch)\n"
            "    return float(loss)    # JL002: host sync per step"
        )),
    "JL003": Rule(
        "JL003", "leaked-tracer-store",
        "Store to self.<attr> or a global from inside a jitted function: "
        "the stored value is a tracer that escapes the trace and raises "
        "(or silently goes stale) when read later.",
        doc="Values inside jit are tracers, not arrays; writing one to "
            "object or module state smuggles it out of the trace. Reads "
            "after tracing see a leaked tracer (UnexpectedTracerError) or "
            "a stale value from the first trace. Return the value instead.",
        example=(
            "@jax.jit\n"
            "def step(self, x):\n"
            "    self.last = x     # JL003: tracer escapes the trace\n"
            "    return x + 1"
        )),
    "JL004": Rule(
        "JL004", "varying-static-arg-in-loop",
        "Jitted call inside a Python loop passing the loop variable at a "
        "static argument position: one full recompile per iteration.",
        doc="static_argnums/static_argnames key the compile cache by VALUE. "
            "Feeding a loop variable into a static position compiles a new "
            "executable every iteration. Make the argument traced, or hoist "
            "the loop into the jitted function.",
        example=(
            "step = jax.jit(run, static_argnums=(1,))\n"
            "for i in range(100):\n"
            "    step(x, i)        # JL004: recompiles 100 times"
        )),
    "JL005": Rule(
        "JL005", "donated-buffer-read",
        "Buffer passed at a donated argument position is read again after "
        "the donating call: donated buffers are invalidated by XLA and "
        "reads return garbage or raise.",
        doc="donate_argnums hands the input buffer to XLA for reuse; the "
            "caller's reference is dead after the call. Rebind the result "
            "over the donated name, or drop the donation.",
        example=(
            "step = jax.jit(run, donate_argnums=(0,))\n"
            "out = step(state, batch)\n"
            "print(state.mean())   # JL005: state was donated"
        )),
    "JL006": Rule(
        "JL006", "fp16-implicit-dtype",
        "jnp array constructor without an explicit dtype inside an fp16 "
        "code path: defaults to float32 and silently upcasts the mixed "
        "expression (or doubles memory) where fp16 was intended.",
        doc="In files on the fp16 path (FP16_PATH_FRAGMENTS), a bare "
            "jnp.zeros/ones/full/arange defaults to float32; downstream "
            "arithmetic then promotes the whole expression. Always pass "
            "dtype= in mixed-precision code.",
        example=(
            "# in .../fp16/loss_scaler.py\n"
            "scale = jnp.zeros((1,))   # JL006: implicit float32"
        )),
    "JL007": Rule(
        "JL007", "collective-axis-mismatch",
        "Collective (psum/pmean/ppermute/...) over an axis name no mesh, "
        "pmap, or shard_map defines; or an axis-name string literal that "
        "duplicates (or conflicts with) the repo's named axis constants.",
        doc="Collectives reduce over a NAMED axis that must be bound by an "
            "enclosing pmap(axis_name=...), shard_map, or Mesh axis tuple; "
            "an unbound name fails at trace time, and a hand-typed string "
            "that drifts from the canonical constant fails only on the "
            "multi-host topology that exercises it. The check resolves "
            "axis arguments through module constants and one level of "
            "helper call (an axis_name parameter is checked at each call "
            "site). Every axis constant must have exactly one defining "
            "module; raw literals that shadow a constant should import it.",
        example=(
            "MODEL_AXIS = \"model\"\n"
            "mesh = Mesh(devs, (MODEL_AXIS,))\n"
            "lax.psum(x, \"modle\")   # JL007: axis 'modle' undefined\n"
            "lax.psum(x, \"model\")   # JL007: literal duplicates MODEL_AXIS"
        )),
    "JL008": Rule(
        "JL008", "interprocedural-donated-read",
        "Buffer passed into a helper that donates it to a jitted call is "
        "read after the helper returns: the donation crosses the call "
        "boundary but the invalidation is just as real.",
        doc="Generalizes JL005 across one call level: pass-1 summarizes, "
            "for every function, which parameters it forwards to a donated "
            "position of a jitted callee (in the same or another module). "
            "A caller that reads its argument after such a helper call is "
            "reading a donated buffer. Rebind the helper's result over the "
            "donated name, or stop donating.",
        example=(
            "_step = jax.jit(_impl, donate_argnums=(0,))\n"
            "def advance(state, x):\n"
            "    return _step(state, x)   # donates its 'state' param\n"
            "new = advance(state, x)\n"
            "err = state - new            # JL008: read after donation"
        )),
    "JL009": Rule(
        "JL009", "rng-key-reuse",
        "The same PRNG key is consumed by two jax.random calls (directly, "
        "through a helper, via an un-split alias, or per-iteration in a "
        "loop without re-splitting): identical randomness where fresh "
        "draws were intended.",
        doc="jax.random keys are single-use: every consuming call "
            "(normal/categorical/...) or split must get a fresh key, then "
            "the name must be rebound from split/fold_in before reuse. The "
            "check tracks key-spends in statement order per suite, follows "
            "keys one call deep (a helper that consumes or splits its key "
            "parameter spends the caller's key), chases un-split aliases "
            "through identity-returning helpers, and flags consuming calls "
            "inside loops whose body never re-derives the key. fold_in is "
            "counter-based derivation and intentionally does not count as "
            "a spend.",
        example=(
            "k = jax.random.PRNGKey(0)\n"
            "a = jax.random.normal(k, (4,))\n"
            "b = jax.random.normal(k, (4,))   # JL009: k reused\n"
            "# correct: k, sub = jax.random.split(k) before each draw"
        )),
    "JL010": Rule(
        "JL010", "quantized-dtype-promotion",
        "An int8 value from the quantization codecs flows into arithmetic "
        "or a matmul without an explicit cast: silent promotion to "
        "float32 defeats the quantization and doubles the hot-path "
        "bandwidth.",
        doc="Values produced by quantize_kv/quantize_tensor are int8 with "
            "a separate scale; mixing them into +,*,-,/ or "
            "jnp.dot/matmul/einsum without .astype()/dequantize first "
            "makes XLA promote the whole expression to float32 — silently "
            "correct-looking, but the int8 path now pays fp32 bandwidth "
            "and the scale is applied to garbage. The taint is seeded from "
            "the quantize_kv/dequantize_kv call graph and follows values "
            "through one call level (helpers that return quantized values, "
            "parameters fed from quantized arguments).",
        example=(
            "qk, scale = quantize_kv(k)\n"
            "attn = jnp.matmul(q, qk)   # JL010: int8 promoted to fp32\n"
            "# correct: jnp.matmul(q, qk.astype(jnp.bfloat16) * scale)"
        )),
    "JL011": Rule(
        "JL011", "partition-spec-conflict",
        "Two PartitionSpec registrations for the same param-tree path "
        "disagree, or a PartitionSpec names a mesh axis no Mesh defines: "
        "the sharding registry would silently resharded (or fail) at "
        "dispatch time.",
        doc="The sharding registry maps param-tree paths to "
            "PartitionSpecs; two modules registering different specs for "
            "the same path means whichever imports last wins and every "
            "consumer reshards. Separately, a spec element must name an "
            "axis some Mesh actually defines — a typo'd axis raises only "
            "when the spec first meets a mesh, usually on the multi-host "
            "job. Specs are resolved through module constants; starred or "
            "computed specs are skipped.",
        example=(
            "SPECS_A = {\"transformer/wq\": PartitionSpec(\"model\", None)}\n"
            "SPECS_B = {\"transformer/wq\": PartitionSpec(None, \"model\")}\n"
            "# JL011: conflicting specs for transformer/wq\n"
            "P = PartitionSpec(\"modle\", None)   # JL011: axis undefined"
        )),
}

ALL_CODES = tuple(sorted(RULES))

# -- JL002 hot-loop registry -------------------------------------------------
# Fully-qualified (posix path suffix, function qualname) pairs the repo
# considers steady-state hot loops: both training engines' per-step core.
# A function is also treated as hot when its `def` line (or the line
# above) carries a `# jaxlint: hot` marker, so new hot loops opt in
# without editing this table: the serving loop's `step`, every jitted
# serving program and both families' `decode_step` carry it.
HOT_LOOPS = (
    ("deepspeed_tpu/runtime/engine.py", "DeepSpeedEngine._train_batch_now"),
    ("deepspeed_tpu/runtime/pipe/engine.py", "PipelineEngine._train_batch_now"),
    # train-step fusion tier: the overlap tap's custom-vjp backward is
    # traced into every fused train step (one reduce per bucket, pinned
    # mid-backward), and the fused step builder assembles the donated
    # jit program itself — a host sync in either serializes every step
    ("deepspeed_tpu/runtime/zero/sharded_optimizer.py",
     "ZeroShardedOptimizer.grad_overlap_tap"),
    ("deepspeed_tpu/runtime/engine.py", "DeepSpeedEngine._get_train_step"),
    # interleaved-1F1B conveyor: the merged schedule's per-tick command
    # stream is what the interpreter executes every train_batch — its
    # construction runs per (M, S, V) change, inside the step path
    ("deepspeed_tpu/runtime/pipe/engine.py",
     "_MergedInterleavedSchedule.__init__"),
    # bucket-streamed ZeRO-Offload: the three-stage host-optimizer
    # pipeline runs once per step on the training thread plus its two
    # workers; any untracked sync or transfer here serializes the step
    ("deepspeed_tpu/runtime/zero/sharded_optimizer.py",
     "ZeroShardedOptimizer._update_host_streamed"),
    ("deepspeed_tpu/runtime/zero/sharded_optimizer.py",
     "_offload_stage_loop"),
)

HOT_MARKER = "jaxlint: hot"

# JL006 applies to fp16 code paths: files whose path contains a component
# matching one of these fragments.
FP16_PATH_FRAGMENTS = ("fp16",)
