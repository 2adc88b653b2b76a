"""Model FLOP/s utilization of training: the FLOPs the forward and
backward of one token need (6 x matmul weights plus causal attention,
bench/work.py; recomputation not counted) times the tokens per second
of the run's window, over chips x the bf16 peak.  Layer: training step.
Moves train_tok_s."""
from bench import work


def read(r):
    rec = r.record
    seq = r.cell.traffic["seq"]
    tok_s = rec["tokens"] / (rec["t1"] - rec["t0"])
    return 100.0 * work.train_flops_per_token(r.spec, seq) * tok_s / (
        r.chips * r.peaks["bf16_flops"])
