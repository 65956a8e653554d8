"""Faults planted in the program, for reading what ``correct`` catches.

Each is a context manager that breaks the timed path underneath a run (the
benchmark's runs never enter one). A cell on one chip can have these:

``state_unchanged``  the cohort step returns every member's snapshot: the
                     client updates are zero;
``half_batch``       each local step takes its loss over the first half of
                     its batch (the mean over the rest): the program family's
                     batch mask drops the second half;
``answer_altered``   the first member of every wave has its update negated
                     where the cohort step produces it.

The exchange between chips does not exist on one chip.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@contextlib.contextmanager
def _engine_output(alter):
    from repro.federated import cohort
    orig = cohort.CohortEngine.cohort_update

    def cohort_update(self, params_stack, cids, lrs, seeds):
        deltas, w = orig(self, params_stack, cids, lrs, seeds)
        return alter(params_stack, deltas, w)

    cohort.CohortEngine.cohort_update = cohort_update
    try:
        yield
    finally:
        cohort.CohortEngine.cohort_update = orig


def _unchanged(params_stack, deltas, w):
    import jax.numpy as jnp
    return jnp.zeros_like(deltas), params_stack


def _negate_first(params_stack, deltas, w):
    deltas = deltas.at[0].set(-deltas[0])
    return deltas, w.at[0].set(params_stack[0] + deltas[0])


@contextlib.contextmanager
def _half_batch(family: str):
    import jax.numpy as jnp
    from repro.models import registry
    fam = registry.get_family(family)

    def masked_batch(xb, yb, vm, cnt):
        half = vm.shape[0] // 2
        vm = vm * (jnp.arange(vm.shape[0]) < half).astype(vm.dtype)
        return fam.masked_batch(xb, yb, vm, jnp.maximum(jnp.sum(vm), 1.0))

    registry.register_family(fam._replace(masked_batch=masked_batch),
                             override=True)
    try:
        yield
    finally:
        registry.register_family(fam, override=True)


def plant(name: str, family: str):
    """The fault ``name`` in a run of the program's model family
    ``family`` (``ModelConfig.family``)."""
    if name == "state_unchanged":
        return _engine_output(_unchanged)
    if name == "answer_altered":
        return _engine_output(_negate_first)
    if name == "half_batch":
        return _half_batch(family)
    raise KeyError(f"unknown fault {name!r}; known: {FAULTS}")
