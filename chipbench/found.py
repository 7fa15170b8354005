"""What the benchmark finds by name under its root: ``configs/<name>.json``,
``traffic/<mix>.json`` and the generator ``traffic/<kind>.py`` it names,
``summaries/<summary>.py``, ``kernel_costs/<kernel>.py`` and
``metrics/<metric>.py``.  A later cell, mix, summary, kernel or metric is
new files, found here, and no edit elsewhere."""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
_loaded: dict = {}


def data(folder: str, name: str, root=HERE) -> dict:
    with open(pathlib.Path(root) / folder / f"{name}.json") as f:
        return json.load(f)


def module(folder: str, name: str, root=HERE):
    path = (pathlib.Path(root) / folder / f"{name}.py").resolve()
    if path not in _loaded:
        if not path.is_file():
            raise FileNotFoundError(f"no {folder} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{folder}_{name}_{len(_loaded)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
