"""A tiny copy of the benchmark for the CPU tests: the same harness, readers
and references, driven by its own BENCHMARK.json, configuration and traffic
files. It is also the proof that a cell, a configuration and a traffic mix
are added by adding files."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def make(tmp, *, chat_rate=20.0):
    """Write the tiny benchmark under ``tmp`` and return its root."""
    root = str(tmp)
    bench = _load("BENCHMARK.json")
    bdir = os.path.join(root, "benchmarks")
    os.makedirs(os.path.join(bdir, "configs"))
    os.makedirs(os.path.join(bdir, "traffic"))
    shutil.copytree(os.path.join(REPO, "benchmarks", "metrics"),
                    os.path.join(bdir, "metrics"))

    bert = _load("benchmarks/configs/bert_large_pretrain.json")
    bert.update(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                max_position_embeddings=64)
    bert["check"]["reference_rows_per_block"] = 4
    # limits read at THIS size on the CPU (sound bf16 runs: loss gaps under
    # 5e-5, sketch gap 0.018, change gap 0.011 over 16 seeds, 1.0 for a step that
    # returns its state unchanged; fp8 control: sketch 0.069+)
    bert["check"]["limits"] = {
        "loss_gap": [5e-4, 5e-4, 5e-4], "first_grad_norm_gap": 0.05,
        "first_grad_sketch_gap": 0.03, "change_norm_gap": 0.035}
    gpt2 = _load("benchmarks/configs/gpt2_large_serve.json")
    gpt2.update(vocab_size=8192, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=4, max_position_embeddings=64)
    gpt2["serving"].update(max_seq_len=64, max_slots=4,
                           prompt_buckets=[8, 32], kv_page_tokens=16)
    # read at this size on the CPU over 765 served tokens of 60 requests:
    # served tokens gap 0.0023-0.0047, the fp8 control 0.018-0.048
    gpt2["check"]["limits"] = {"served_logit_gap": 0.009}
    for name, cfg in (("bert_large_pretrain", bert),
                      ("gpt2_large_serve", gpt2)):
        with open(os.path.join(bdir, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)

    for name in ("s128_mb64_1chip", "s128_mb64_zero2_dp4"):
        t = _load(f"benchmarks/traffic/{name}.json")
        t.update(seq_len=16, micro_batch_per_chip=4, trace_seconds=0.2)
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)
    chat = _load("benchmarks/traffic/chat_steady.json")
    chat.update(
        arrivals={"process": "poisson", "rate_per_s": chat_rate},
        prompt_tokens={"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 32},
        output_tokens={"dist": "lognormal", "median": 6, "sigma": 0.6,
                       "min": 2, "max": 16},
        max_total_tokens=64, warm_seconds=0.5, trace_seconds=0.3,
        check_requests=4)
    closed = _load("benchmarks/traffic/longprompt_closed.json")
    closed.update(
        clients=4, requests_per_client=200,
        prompt_tokens={"dist": "uniform", "min": 12, "max": 30},
        output_tokens={"dist": "uniform", "min": 8, "max": 16},
        max_total_tokens=64, warm_seconds=0.5, trace_seconds=0.3,
        check_requests=96)
    for name, t in (("chat_steady", chat), ("longprompt_closed", closed)):
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(t, f)

    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
