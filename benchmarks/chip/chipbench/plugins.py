"""Files the harness finds by name: ``<kind>/<name>.py`` under the
benchmark's directory, each loaded on its own.

The kinds are ``metrics`` (a ``read(run)``), ``demand`` (a
``generate(rng, *, n, **knobs)``), ``drivers`` (a ``warm_up`` and a
``run``) and ``servers`` (a ``make(config)``). A later cell that needs a
new one adds a file; no file that is there changes.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]

_loaded: dict[tuple[str, str], ModuleType] = {}


def load(kind: str, name: str) -> ModuleType:
    """The module in ``<kind>/<name>.py``."""
    key = (kind, name)
    if key not in _loaded:
        path = BENCH_DIR / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file {path}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]
