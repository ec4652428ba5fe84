"""Device time of the scan engine's mega-batch program per mega-batch."""
from chipbench import trace

PATTERNS = ("jit_megabatch_fn", "jit_timed_megabatch")


def read(t, record):
    return trace.module_ms_per_megabatch(t, PATTERNS)
