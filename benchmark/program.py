"""The one place the benchmark touches the program's constructors: from a
configuration file's ``model`` section to the program's model object."""

from __future__ import annotations

import json


def build_model(model: dict):
    """The program's model for a configuration file's ``model`` section."""
    from raft_ncup_tpu.config import model_config_from_json
    from raft_ncup_tpu.models import get_model

    return get_model(model_config_from_json(json.dumps(model)))


def executable_memory(fwd) -> list:
    """XLA's ``memory_analysis()`` of every program a ``ShapeCachedForward``
    compiled, as its cost ledger banked it at compile time."""
    out = []
    for key in fwd.costs.keys():
        entry = fwd.costs.entry(key) or {}
        if entry.get("memory_stats"):
            out.append({"key": key, **entry["memory_stats"]})
    return out
