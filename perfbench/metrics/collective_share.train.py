"""Share of the device's op self time in the traced window spent in
collectives (all-gather, all-reduce, reduce-scatter, collective-permute,
all-to-all), in %: ``collective_share`` of ``perfbench/trace.py``."""


def read(r):
    share = r["summary"].get("collective_share")
    return None if share is None else 100.0 * share
