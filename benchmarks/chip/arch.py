"""A configuration file and the family module that reads it.

``configs/<name>.json`` keeps the keys of the model's published
``config.json`` and names its ``family``, one of the program's
``ArchConfig.family`` names.  ``families/<family>.py`` reads the file into
that family's ``Arch`` (a frozen dataclass of sizes with at least
``n_layers``, ``d_model``, ``vocab`` and ``dtype``) and provides what the
benchmark needs of one kind of model:

* ``arch_of(cfg)``: the configuration's ``Arch``;
* ``program_sizes(arch)``: ``{ArchConfig attribute: value}`` that the
  program's model must have;
* ``shapes(arch)``: the weight table ``weights.make`` draws from;
* ``logits(arch, params, tokens, positions, quantize="")``: the plain
  float32 reference at ``highest`` precision;
* ``window_work(arch, recs, chunk, dispatches)``: ``work.Work`` by layer
  of the window's requests, and ``model_flops``.

A family module imports nothing of the program under test.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
_FAMILIES: dict = {}


def load_config(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def load_family(name: str, root: Path = HERE):
    """The module ``families/<name>.py`` under ``root``, loaded once."""
    path = (root / "families" / f"{name}.py").resolve()
    if path not in _FAMILIES:
        if not path.is_file():
            raise FileNotFoundError(f"no module for family {name!r} at {path}")
        # one module name per file: two roots may each hold a ``dense``
        mod_name = f"bench_family_{len(_FAMILIES)}_" + \
            name.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod     # dataclasses look their module up
        spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]
