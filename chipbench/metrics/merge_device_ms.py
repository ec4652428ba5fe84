"""Device time of the Alg. 2 merge program per mega-batch, its psum over
the replica mesh included on four chips."""
from chipbench import trace

PATTERNS = ("jit_merge_fn", "jit_merge_sharded")


def read(t, record):
    return trace.module_ms_per_megabatch(t, PATTERNS)
