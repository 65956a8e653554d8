"""Operations and bytes a step needs, computed from shapes.

A model's own counts live in its family module (``bench.families``):
``forward_flops`` for one sample's forward pass and ``train_flops`` for the
window's real local SGD. What is here does not depend on the model.
"""
from __future__ import annotations

from bench import families


def trained_params(config: dict) -> int:
    """The weights the FedPSA server holds per model: the flat vector of
    the family's trained leaves."""
    from bench import reference
    return int(sum(reference.leaf_sizes(config)))


def sens_sketch_bytes(d: int, rows: int) -> float:
    """``sens_sketch``: per sketched model reads theta, g and the Fisher
    diagonal, three f32 vectors of the model's size."""
    return float(rows) * 3.0 * d * 4


def check(config: dict) -> None:
    """The configuration's shapes give the parameter count it states."""
    have = int(families.load(config).params(config))
    if have != int(config["params"]):
        raise ValueError(f"{config['name']}: shapes give {have} "
                         f"parameters, the file states {config['params']}")
