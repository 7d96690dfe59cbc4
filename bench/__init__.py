"""The benchmark: one harness (``bench/run.py``) and files found by name.

A configuration's ``data.kind`` names ``bench/data/<kind>.py``, its
``system`` names ``bench/systems/<system>.py``; a traffic mix's ``kind``
names ``bench/loops/<kind>.py``, and each of its query types' ``op`` names
``bench/ops/<op>.py``.  A cell is added by adding such files and one
``workloads`` entry; the harness itself stays as it is.
"""
from __future__ import annotations

import importlib


def by_name(group: str, name: str):
    """The module ``bench/<group>/<name>.py``."""
    try:
        return importlib.import_module(f"bench.{group}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.{group}.{name}":
            raise ValueError(f"no bench/{group}/{name}.py") from None
        raise
