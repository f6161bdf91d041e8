"""Plain references of the benchmark's configurations.

A configuration names the module of its plain model in its
``reference`` key (``bench/ref/decoder.py``).  Such a module has
``init_weights(cfg, seed)``, ``loss(cfg, weights, tokens, dtype)``, and
the counts ``bench/flops.py`` reads: ``param_count(cfg)`` (the size of
the packed plane) and ``flops_per_token(cfg, seq_len)`` (model FLOPs of
a forward and backward pass).  A configuration with a block of its own
brings its own module.  ``ltadmm`` runs the solver's rounds over any of
them.
"""
from __future__ import annotations

import importlib
from pathlib import Path


def module(config):
    """The plain model module that ``config["reference"]`` names."""
    return importlib.import_module(
        f"{__name__}.{Path(config['reference']).stem}")
