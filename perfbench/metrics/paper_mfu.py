"""Model FLOP/s utilisation of the paper's round: every worker's
full-batch gradient (U x K samples, forward and backward of the MLP's
matmuls) times the window's rounds per second, over the chips' bf16
peak, in %."""
from perfbench import flops, peaks


def read(r):
    rate = r["window"]["metrics"].get("paper_rounds_per_s")
    if rate is None:
        return None
    rnd = r["config"]["round"]
    per_round = (rnd["workers"] * rnd["samples_per_worker"]
                 * flops.mlp_per_sample(r["config"]["model"]["sizes"]))
    peak = peaks.peak(r["device"]["kind"]).bf16_flops * r["chips"]
    return 100.0 * rate * per_round / peak
