"""Faults planted under the timed path, for the readings that set each
limit's upper end (``calibrate.py``, on the chip) and for the test that sees
``correct`` come out false (``tests/test_faults.py``, on the CPU).

Each is a context manager that patches the program for the trainers built
inside it. Every trainer builds its jitted programs anew, so a trainer built
inside the context traces the patched code. A fault that breaks a part
particular to one model family, such as where its batches are packed, is a
function of that name in the family's module (``families/<family>.py``).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(module, name, replacement):
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

    return patched(trainer, "sgd_update", replacement)


def no_exchange():
    """The cross-replica sum over the replica mesh is left out: each chip
    keeps its own partial (the merge's psum and the metrics' psum)."""
    from repro.utils import tree

    def replacement(original):
        def replica_all_sum(x, axis_name=None):
            return x
        return replica_all_sum

    return patched(tree, "replica_all_sum", replacement)


FAULTS = {
    "unchanged_state": unchanged_state,
    "no_exchange": no_exchange,
}


def plant(name: str, family):
    """The fault ``name``, planted: the family's own where its module
    defines one, else the program-wide one of ``FAULTS``."""
    return (getattr(family, name, None) or FAULTS[name])()


def applicable(traffic: dict) -> list:
    """The faults a cell of this traffic can have."""
    names = ["unchanged_state", "half_batch"]
    if traffic["placement"] == "sharded":
        names.append("no_exchange")
    return names
