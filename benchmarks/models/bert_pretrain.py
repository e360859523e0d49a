"""The system under test for BERT pretraining cells, reached through the
entry points a trainer calls: ``deepspeed_tpu.initialize`` and
``engine.train_step``. This file is the only place the training cells touch
the program; it hands over weights the benchmark made and reads back losses
and the optimizer's state."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.refs import weights as weights_mod


def ds_config(cfg, traffic, chips):
    train = cfg["training"]
    micro = int(traffic["micro_batch_per_chip"])
    return {
        "train_batch_size": micro * chips,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "Adam", "params": {
            "lr": train["optimizer"]["lr"],
            "betas": train["optimizer"]["betas"],
            "eps": train["optimizer"]["eps"]}},
        "bf16": {"enabled": train["compute_dtype"] == "bfloat16"},
        "zero_optimization": {
            "stage": int(traffic["zero_stage"]) if chips > 1 else 0},
        "activation_checkpointing": {
            "enabled": bool(train["activation_checkpointing"])},
        # the cell's chips, however many the host shows
        "mesh": {"data_parallel_size": chips},
    }


class Program:
    """One engine with its compiled step and state: set-up builds it, drives
    it through the checked first steps, and hands THIS object to the
    window."""

    def __init__(self, cfg, traffic, chips, flat_weights):
        import deepspeed_tpu
        from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining

        _quiet()
        self.cfg = cfg
        self.chips = chips
        model_cfg = BertConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            hidden_dropout_prob=cfg["hidden_dropout_prob"],
            attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
            pre_layer_norm=cfg["pre_layer_norm"],
            checkpoint_policy=cfg["training"]["checkpoint_policy"])
        self.names = list(weights_mod.flatten(weights_mod.nest(flat_weights)))
        params = weights_mod.nest(flat_weights)
        self.engine, _, _, _ = deepspeed_tpu.initialize(
            model=BertForPreTraining(model_cfg), model_parameters=params,
            config_params=ds_config(cfg, traffic, chips))
        self.global_batch = int(traffic["micro_batch_per_chip"]) * chips

    def step(self, batch):
        """One optimizer step on one fresh batch; returns the loss as a
        device scalar without waiting for it."""
        return self.engine.train_step([batch])

    # -- reading the state back (set-up only) ---------------------------
    def _shapes(self):
        from benchmarks.refs import bert_pretrain_ref as ref

        shapes = ref.weight_shapes(self.cfg)
        return [shapes[n] for n in self.names]

    def _leaves(self, state):
        """The leaves of ``state`` in name order: of a tree shaped like the
        parameters, or of ZeRO's flat vector (the leaves concatenated, then
        padding). Traceable."""
        if isinstance(state, dict):
            flat = weights_mod.flatten(state)
            return [flat[n] for n in self.names]
        out, lo = [], 0
        for shape in self._shapes():
            n = int(np.prod(shape))
            out.append(state[lo:lo + n])
            lo += n
        return out

    def _per_leaf_fn(self, fn):
        """The jitted function that maps ``fn(name, leaf, ...)`` over the
        leaves of its arguments (states as ``_leaves`` takes them)."""
        return jax.jit(lambda *xs: [
            fn(name, *leaves) for name, *leaves in zip(
                self.names, *(self._leaves(x) for x in xs))])

    def _per_leaf(self, fn, *states):
        """``fn(name, leaf, ...)`` of each leaf (of each of ``states``, leaf
        by leaf), in one jitted call, by leaf name."""
        out = self._per_leaf_fn(fn)(*states)
        return dict(zip(self.names, (np.asarray(x) for x in out)))

    def _first_moment(self):
        state = self.engine.opt_state
        return getattr(state, "inner_state", state).exp_avg

    def first_moment_norms(self):
        """Norm of each leaf of Adam's first moment, by leaf name."""
        sq = self._per_leaf(lambda _name, m: jnp.sum(jnp.square(m)),
                            self._first_moment())
        return {k: float(np.sqrt(v)) for k, v in sq.items()}

    def first_moment_sketch(self):
        """The reference's ``sketch`` of each leaf of Adam's first moment."""
        from benchmarks.refs import bert_pretrain_ref as ref

        return self._per_leaf(lambda _name, m: ref.sketch(m),
                              self._first_moment())

    @staticmethod
    def _sum_sq_change(skip):
        """``fn(name, now, before)``: the sum of squares of a leaf's change
        over all elements but those ``skip`` names. Flat all the way: a
        second axis on a slice of ZeRO's flat vector makes XLA re-view the
        whole vector in tiles (43 GB for an axis of 2)."""
        def sum_sq(name, a, b):
            d = jnp.square(a.reshape(-1).astype(jnp.float32)
                           - b.reshape(-1).astype(jnp.float32))
            if name in skip:
                period, lo, hi = skip[name]
                col = jax.lax.iota(jnp.int32, d.shape[0]) % period
                d = jnp.where((col >= lo) & (col < hi), 0.0, d)
            return jnp.sum(d)
        return sum_sq

    def change_norms(self, initial_flat, skip):
        """Norm of each leaf's change from ``initial_flat`` (of the float32
        master weights where the engine keeps them apart). ``skip`` names,
        by leaf, the elements left out: ``(period, lo, hi)``, those whose
        place in the flattened leaf modulo ``period`` falls in [lo, hi)."""
        master = getattr(self.engine.opt_state, "flat_master", None)
        now = (master if master is not None and master.size > 0
               else self.engine.params)
        sq = self._per_leaf(self._sum_sq_change(skip), now,
                            weights_mod.nest(initial_flat))
        return {k: float(np.sqrt(v)) for k, v in sq.items()}

    def close(self):
        """Free the program's state on the device (the reference runs after
        this, in the memory it leaves)."""
        engine, self.engine = self.engine, None
        for leaf in jax.tree_util.tree_leaves(
                (engine.params, engine.opt_state, engine.scaler_state)):
            if hasattr(leaf, "delete"):
                leaf.delete()


def _quiet():
    """The program logs at INFO to standard output; keep warnings only."""
    import logging

    logging.getLogger("DeepSpeedTPU").setLevel(logging.WARNING)
