"""Model FLOP/s utilisation of the Zamba2 training round: model FLOPs per
token (``perfbench/flops_zamba2.py``: forward and backward, recompute
excluded, attention over the causal half) times the window's training
tokens per second, over the bf16 peak of all the cell's chips, in %.

Each chip of a worker's model shards computes the worker's whole batch
(the round's forward and backward are replicated over the model axis), so
three of every four chips' work in a 1 x 4 cell counts as lost here, as
does the masked half of the attention scores the program computes."""
from perfbench import flops_zamba2, peaks


def read(r):
    rate = r["window"]["metrics"].get("train_tokens_per_s")
    if rate is None or "attention_head_dim" not in r["config"]:
        return None
    per_token = flops_zamba2.per_token(r["config"], r["traffic"]["seq"])
    peak = peaks.peak(r["device"]["kind"]).bf16_flops * r["chips"]
    return 100.0 * rate * per_token / peak
