"""Model FLOP/s utilisation of the training round: model FLOPs per token
(forward and backward, recompute excluded) times the window's training
tokens per second, over the chips' bf16 peak, in %."""
from perfbench import flops, peaks


def read(r):
    rate = r["window"]["metrics"].get("train_tokens_per_s")
    if rate is None:
        return None
    per_token = flops.mamba2_per_token(r["config"]["model"],
                                       r["traffic"]["seq"])
    peak = peaks.peak(r["device"]["kind"]).bf16_flops * r["chips"]
    return 100.0 * rate * per_token / peak
