"""Load one per-layer reader from another: `layer_metrics/<name>.py` files
carry dots in their names and are found by path (as `run.py` finds them),
so a reader that builds on a sibling asks for it here."""

from __future__ import annotations

import functools
import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def load_reader(name: str):
    """The module of `layer_metrics/<name>.py`."""
    path = os.path.join(_HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
