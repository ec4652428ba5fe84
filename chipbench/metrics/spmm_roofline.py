"""Share of the spmm kernel's roofline: the least time for the work of every
call in the traced window (training and evaluation), counted from the
batches by ``work.spmm_work``, over the kernel's device time."""
from chipbench import trace

PATTERNS = ("%spmm_replicated",)


def read(t, record):
    seconds = trace.op_seconds(t, PATTERNS)
    if seconds <= 0:
        return None
    return 100.0 * t["work"]["spmm_least_s"] / seconds
