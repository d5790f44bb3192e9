"""Forced kernel configs: the only thing that overrules a family's rule.

A kernel family's tile, block or fused-or-scan choice is a function of
its shapes and dtype, written once in tune/space.py. `force()` /
`forcing()` pin one family's config process-wide; `space.pick` then
hands the kernel that config where it is legal at the shape (and warns
and turns the family off where it is not: someone pinned exactly that
tile, and another in its place would falsify their sweep). Tests and
the sweep tool (tune/harness.py) are the callers. No table, file,
environment variable or flag is read here.

`forced_key()` is the piece the Executor folds into its jit cache key,
so a change of a forced config re-traces instead of reusing a step
program built with the old tile.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, NamedTuple, Optional, Tuple


class Override(NamedTuple):
    config: Dict[str, Any]
    source: str  # "forced"


_lock = threading.RLock()
_forced: Dict[str, Dict[str, Any]] = {}


def force(kernel: str, config: Optional[Dict[str, Any]]) -> None:
    """Pin (or with None, unpin) a kernel family's config
    process-wide. Takes effect at the next trace: the Executor's cache
    key includes forced_key(), so the next run() re-traces."""
    with _lock:
        if config is None:
            _forced.pop(kernel, None)
        else:
            _forced[kernel] = dict(config)


@contextlib.contextmanager
def forcing(kernel: str, config: Optional[Dict[str, Any]]):
    """Scoped force(): the harness traces each candidate under this."""
    with _lock:
        prev = _forced.get(kernel)
    force(kernel, config)
    try:
        yield
    finally:
        force(kernel, prev)


def forced_config(kernel: str) -> Optional[Override]:
    with _lock:
        cfg = _forced.get(kernel)
    return None if cfg is None else Override(dict(cfg), "forced")


def forced_key() -> Tuple:
    """The forced configs as a hashable tuple, () when nothing is."""
    with _lock:
        return tuple((k, tuple(sorted(_forced[k].items())))
                     for k in sorted(_forced))


def reset() -> None:
    """Test isolation: clear the forced configs."""
    with _lock:
        _forced.clear()
