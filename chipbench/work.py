"""Work counted from the problem, not from an implementation, and the
chip's peaks. Any later kernel for the same call is held to these counts.

Roofline share of a kernel = the least time the chip could take for the
work (the larger of FLOPs / peak FLOP/s and bytes / peak HBM bandwidth),
summed over the calls, divided by the kernel's device time.
"""
from __future__ import annotations

import numpy as np

F32 = 4  # bytes

# Per chip. Source: Google Cloud documentation, "TPU v5e" (bf16 peak
# 197 TFLOP/s, HBM 16 GB at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; a kind not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


def least_seconds(flops: float, bytes_: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(flops / p["flops"], bytes_ / p["hbm_bytes_per_s"])


def spmm_work(feat_idx: np.ndarray, feat_mask: np.ndarray,
              sample_mask: np.ndarray, hidden: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one padded-COO batch times a dense (NF, hidden) W.

    Each distinct W row the valid slots touch is read once; the index and
    value of each valid slot are read once; each valid sample's output row
    is written once. FLOPs are 2 x valid nnz x hidden. Padding slots and
    padding samples are no work.
    """
    valid = feat_mask & sample_mask[:, None]
    nnz = int(valid.sum())
    rows = len(np.unique(feat_idx[valid]))
    n_out = int(sample_mask.sum())
    bytes_ = rows * hidden * F32 + nnz * (4 + F32) + n_out * hidden * F32
    return 2.0 * nnz * hidden, float(bytes_)


def weighted_merge_bytes(n_params: int, n_replicas: int, momentum: bool) -> float:
    """Bytes of one weighted merge of ``n_replicas`` replicas of
    ``n_params`` float32 parameters: each replica read once, the global and
    previous global read once where the formula has the momentum term, the
    merged model written once. (Under the sharded placement each chip merges
    its own replica without the momentum term, which follows the psum.)"""
    reads = n_replicas + (2 if momentum else 0)
    return float((reads + 1) * n_params * F32)


def weighted_merge_flops(n_params: int, n_replicas: int, momentum: bool) -> float:
    """A multiply and an add per replica element; the momentum term adds a
    subtract, a multiply and an add per element."""
    return float(n_params * (2 * n_replicas + (3 if momentum else 0)))


def model_flops_per_sample(nnz: int | np.ndarray, hidden: int,
                           n_classes: int) -> np.ndarray:
    """Training FLOPs of one sample: the head's matmul forward and its two
    backward matmuls (6 H C), and the sparse input layer forward and its
    weight gradient (4 nnz H). Biases, softmax and the update are left out."""
    return 6.0 * hidden * n_classes + 4.0 * np.asarray(nnz, np.float64) * hidden
