"""The chip benchmark's harness: traffic, weights, trace reduction, FLOP and
byte counts, peaks and the comparison that decides ``correct``."""

import importlib.util
import pathlib


def load_module(path: pathlib.Path, name: str):
    """A module from a file found by name (a reference, a metric reader)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
