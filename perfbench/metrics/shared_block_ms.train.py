"""One call of a shared block alone, forward and backward, ms per call:
the median of the path driver's ``probe.shared_block`` spans of back-to-
back calls, each span at least 250 ms of host clock. A call is what the
round runs for a hybrid layer: the model-axis gathers of the block's and
the call's weights, [h, embedding], the block, its adapter and its
``linear``, under the round's remat, on the round's mesh and shapes.

No Pallas kernel runs in it (none is on the round's path), so there is no
kernel roofline share beside it."""
import statistics


def read(r):
    samples = r["measures"].get("shared_block_ms")
    return statistics.median(samples) if samples else None
