"""Share of the weighted_merge kernel's roofline: the least time for the
bytes of every merge in the traced window (``work.weighted_merge_bytes``)
over the kernel's device time."""
from chipbench import trace

PATTERNS = ("%weighted_merge",)


def read(t, record):
    seconds = trace.op_seconds(t, PATTERNS)
    if seconds <= 0 or t["work"]["weighted_merge_least_s"] <= 0:
        return None
    return 100.0 * t["work"]["weighted_merge_least_s"] / seconds
