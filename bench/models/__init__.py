"""Plain references of the benchmark's model configurations.

One module per ``model`` named in ``bench/configs/<name>.json``.  Each gives
the parameter shapes, the maskable units, a straightforward forward pass and
the layer table that ``bench/flops.py`` counts from.  Nothing here imports
the program under test.
"""
import importlib


def load(model: str):
    """The reference module of one architecture, by its config's name."""
    return importlib.import_module(f"bench.models.{model}")
