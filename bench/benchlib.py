"""Paths and by-name loading shared by the harness, drivers and readers.

Configurations, drivers, references and metric readers are files named
after entries of ``BENCHMARK.json`` (names may hold ``-`` and ``.``), so
they are loaded by path rather than imported as packages.
"""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str):
    """Import the Python file at ``path`` (once per process)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_file(*parts: str):
    """``bench/<parts...>`` loaded as a module."""
    return load_module(os.path.join(BENCH, *parts))


def use_program() -> None:
    """Make the program (``<root>/src``) importable."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
