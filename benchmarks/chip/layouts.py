"""Each model layout's part of the benchmark, in a file of its own:
``arch/<layout>.py``, found by a model config's ``layout`` as a per-layer
metric's reader is found by its name (``layers/<metric>.py``).

A new architecture adds one such file, a config file that names its
layout, and a cell; no other file of the benchmark changes.  The module
gives, written from the architecture's equations and importing nothing of
the program:

* weights (``weights.py``): ``leaf_specs(cfg)``, every leaf of the
  parameter tree, embedding, head and final norm included, in the order
  the weights are drawn;
* the plain reference (``reference.py``): ``arch(cfg)``, the hashable
  ``reference.Arch`` the reference is compiled for; ``hidden(params,
  tokens, a, quant)``, the final-normed float32 hidden states (B, T, d);
  and ``head(params)``, the (d, V) matrix the logits multiply by;
* operation counts (``flops.py``): ``layer_matmul_params(cfg)``,
  ``weight_bytes(cfg)``, ``token_flops(cfg, pos)``, ``state_bytes(cfg,
  pos)`` and ``param_count(cfg)``.
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys
from types import ModuleType
from typing import Dict

ARCH_DIR = pathlib.Path(__file__).resolve().parent / "arch"
_LOADED: Dict[pathlib.Path, ModuleType] = {}


def module(layout: str) -> ModuleType:
    """``arch/<layout>.py``, loaded once; raises naming the file where
    there is none."""
    path = ARCH_DIR / f"{layout}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise ValueError(f"no architecture module for layout "
                             f"{layout!r}: {path} does not exist")
        spec = importlib.util.spec_from_file_location(f"arch_{layout}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod     # dataclasses look their module up
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(cfg: dict) -> ModuleType:
    """The architecture module of one model's config."""
    return module(cfg["layout"])
