"""The benchmark's parts that are found by name: ``bench/<kind>/<name>.py``.

A per-layer metric's reader is ``metrics/<metric>.py``; a model's plain
reference and work count are ``models/<model>.py``, for the ``model`` key of
a configuration. A later metric or model is a new file; a name with no file
is an error that names the file looked for, never a fallback.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: dict[tuple[str, str], object] = {}


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once a process."""
    path = BENCH / kind / f"{name}.py"
    if not NAME.match(name) or not path.is_file():
        raise ValueError(f"bench: no file bench/{kind}/{name}.py for {name!r}")
    if (kind, name) not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[.-]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[kind, name] = mod
    return _loaded[kind, name]
