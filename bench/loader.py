"""Load a module of the benchmark from its file: a model family, a metric's
reader or a kernel's operation count. Each file runs once a process."""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path


@functools.lru_cache(maxsize=None)
def load(path: Path):
    """The module in ``path`` (``bench/<kind>/<name>.py``)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
