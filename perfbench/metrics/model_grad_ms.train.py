"""The model's forward and backward alone, ms per call: the median of the
path driver's timed spans of back-to-back ``ZooTrainRound.grads_in_layout``
calls, each span at least 250 ms of host clock."""
import statistics


def read(r):
    samples = r["measures"].get("model_grad_ms")
    return statistics.median(samples) if samples else None
