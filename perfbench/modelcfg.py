"""A configuration file (``configs/<name>.json``, sizes under the published
``config.json`` key names), the family module it names, and the fold's byte
count.

Each configuration states ``"family"``: the kind of model, whose module
``families/<family>.py`` reads the sizes and provides the weights, the
reference loss and the counts (``families/dense.py`` lists what a family
module provides).  A new kind of model is a new family file; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_family(cfg: dict):
    """The family module ``cfg`` names, loaded by file path (once per
    process)."""
    name = cfg.get("family")
    if not name:
        raise ValueError(f"configuration {cfg.get('name')!r} names no "
                         f"\"family\"")
    path = os.path.join(HERE, "families", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r}: family {name!r} has no "
            f"module {path}")
    modname = "perfbench_family_" + re.sub(r"\W", "_", name)
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return sys.modules[modname]


def fold_bytes(n_params: int, clients: int, delta_bytes: int = 2) -> int:
    """HBM bytes one executor's local fold of ``clients`` deltas of a model
    of ``n_params`` parameters needs: each delta read once in its own dtype,
    the fp32 accumulator read and written once.  The same count whatever
    implements the fold."""
    return clients * n_params * delta_bytes + 2 * 4 * n_params
