"""Model FLOP/s utilization: tokens per second per chip times the forward +
backward FLOPs a token needs (recomputation not counted; the benchmark's own
arithmetic in ``harness/costs.py``) over the chip's bf16 peak."""

from benchmarks.harness import costs


def read(run):
    rate = run.host.get("tokens_per_s_chip")
    if rate is None:
        return None
    cfg = run.cell.config
    flops = costs.train_flops_per_token(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], seq_len=run.cell.traffic["seq_len"],
        vocab=cfg["vocab_size"])
    peak = costs.peaks_for(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * rate * flops / peak
