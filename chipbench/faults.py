"""Faults planted under the timed path, for the readings that set each
limit's upper end (``calibrate.py``, on the chip) and for the test that sees
``correct`` come out false (``tests/test_faults.py``, on the CPU).

Each is a context manager that patches the program for the trainers built
inside it. Every trainer builds its jitted programs anew, so a trainer built
inside the context traces the patched code.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(module, name, replacement):
    original = getattr(module, name)
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def unchanged_state():
    """Every SGD step returns the parameters it was given."""
    from repro.core import trainer

    def replacement(original):
        def sgd_update(params, grads, lr, cfg=None, momentum_state=None, **kw):
            return params, momentum_state
        return sgd_update

    return _patched(trainer, "sgd_update", replacement)


def half_batch():
    """Each packed batch keeps only the first half of its valid samples;
    the loss is then the mean over the rest."""
    from repro.data import providers

    def replacement(original):
        def stack_lazy_plan(*args, **kwargs):
            out = original(*args, **kwargs)
            mask = out["sample_mask"]
            keep = (np.arange(mask.shape[-1])
                    < -(-mask.sum(axis=-1, keepdims=True) // 2))
            mask &= keep
            return out
        return stack_lazy_plan

    return _patched(providers, "stack_lazy_plan", replacement)


def no_exchange():
    """The cross-replica sum over the replica mesh is left out: each chip
    keeps its own partial (the merge's psum and the metrics' psum)."""
    from repro.utils import tree

    def replacement(original):
        def replica_all_sum(x, axis_name=None):
            return x
        return replica_all_sum

    return _patched(tree, "replica_all_sum", replacement)


FAULTS = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "no_exchange": no_exchange,
}


def applicable(traffic: dict) -> list:
    """The faults a cell of this traffic can have."""
    names = ["unchanged_state", "half_batch"]
    if traffic["placement"] == "sharded":
        names.append("no_exchange")
    return names
