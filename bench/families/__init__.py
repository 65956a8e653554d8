"""Family modules: everything of the benchmark that depends on the model.

A configuration file (``bench/configs/<name>.json``) names its family
module by the program's family, its ``family`` key: the module is
``bench/families/<family>.py``. Adding a model family to the benchmark
means adding such a module and configuration files of that family; nothing
else in ``bench/`` knows one family from another.

A family module imports nothing of the program except inside
``program_inputs``, and holds:

``FIELDS``         the configuration keys checked against the program's
                   ``ModelConfig`` of ``program_config``;
``BATCH_KEYS``     the two keys of a batch dict, (features, labels), as the
                   program's loss reads them and the calibration batch holds;
``params(config)``  every weight the shapes give (checked against the file's
                   ``params`` and the program's ``count_params``);
``leaf_names(config)``  the trained leaves, as key paths, in the order of the
                   flat parameter vector the FedPSA server works on;
``leaf_shapes(config)``  {path: shape} of those leaves;
``init_weights(config, seed)``  the run's initial weights, on the device, in
                   one jitted call;
``forward(params, x, config, dtype)`` and
``loss(params, batch, weight, config, dtype)``  the plain reference, f32 at
                   ``Precision.HIGHEST`` (``dtype`` float32) or the control;
``make_world(config, traffic, seed)``  a ``bench.traffic.World``;
``program_inputs(world)``  the program's client datasets and test set;
``forward_flops(config, traffic)``  one sample's forward operations;
``train_flops(config, traffic, counters)``  the operations of the window's
                   real local SGD (``counters`` from ``bench.probe``).
"""
from __future__ import annotations

import importlib
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_]*")


def load(config: dict):
    """The family module a configuration names."""
    name = config["family"]
    if not _NAME.fullmatch(name) or not os.path.exists(
            os.path.join(HERE, f"{name}.py")):
        raise KeyError(f"{config['name']}: no family module {name!r} in "
                       f"bench/families/")
    return importlib.import_module(f"bench.families.{name}")
