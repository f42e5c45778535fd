"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device-op intervals, averaged over the chips) /
(window), from the profiler trace."""


def read(r):
    share = r["summary"].get("idle_share")
    return None if share is None else 100.0 * share
