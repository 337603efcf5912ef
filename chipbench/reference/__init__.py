"""Plain references of the benchmark's configurations, one module per
architecture; a configuration file names its module under "reference"."""
from __future__ import annotations

import importlib


def load_model(spec: dict, params: dict):
    mod = importlib.import_module(f"chipbench.reference.{spec['reference']}")
    return mod.Model(spec, params)
