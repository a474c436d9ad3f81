"""Architecture families: the benchmark's code that depends on how a model
is built, one module per family, found by name.

A configuration file names its family with a ``"family"`` key, and
``of(sizes)`` imports ``bench/families/<family>.py``. A family module has:

* ``KEYS``: the keys of the sizes it reads, ``dtype`` among them (the type
  the weights are served in). ``freeze`` keeps exactly these and the
  family's name as the static argument of the jitted weights and reference
  programs;
* ``program_fields(sizes) -> dict``: the program's ``ModelConfig`` fields
  for the sizes, set over the published config of ``sizes["arch"]``; it
  raises ``SystemExit`` where the program cannot run the sizes;
* ``leaf_specs(sizes)``: ``(path, shape, kind, scale)`` of every weight in
  a fixed order; a leaf's random stream is its position in the list;
* ``forward(sizes, params, prefix, inputs, low)``: the plain float32
  reference's final hidden states ``(n, T, D)`` of the rows of ``inputs``
  after the shared ``prefix``, every matmul operand rounded to float8 where
  ``low``; it imports nothing of the program;
* ``matmul_params(sizes)``, ``token_flops(sizes, attended)`` and
  ``kernel_call(sizes, batch, selected)``: the operations and bytes that
  ``bench/costs.py`` gives the metric readers;
* ``tiny(sizes) -> sizes``: the sizes cut to what the CPU tests run.

So a configuration of a new architecture comes as new files: its module
here, its file in ``bench/configs/``, its cells and traffic.
"""

from __future__ import annotations

import importlib
import json
import pathlib

CONTRACT = ("KEYS", "program_fields", "leaf_specs", "forward",
            "matmul_params", "token_flops", "kernel_call", "tiny")


def of(sizes: dict):
    """The family module that ``sizes`` names."""
    return importlib.import_module(f"{__name__}.{sizes['family']}")


def load(path) -> dict:
    """A configuration file's sizes. Stops, naming the file, where it names
    no family, or one that is not here or lacks part of the contract."""
    sizes = json.loads(pathlib.Path(path).read_text())
    name = sizes.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise SystemExit(f"{path}: no \"family\" key naming a module of "
                         f"bench/families/ (found {name!r})")
    try:
        module = of(sizes)
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise SystemExit(f"{path}: unknown family {name!r}, no "
                         f"bench/families/{name}.py") from None
    missing = [n for n in CONTRACT if not hasattr(module, n)]
    if missing:
        raise SystemExit(f"{path}: family {name!r} lacks {missing}")
    return sizes


def freeze(sizes: dict) -> str:
    """The family's name and the sizes it reads, as a hashable static
    argument of ``jax.jit`` (JSON, so lists and nested groups keep)."""
    keys = of(sizes).KEYS
    return json.dumps(dict({k: sizes[k] for k in keys},
                           family=sizes["family"]), sort_keys=True)


def thaw(frozen: str) -> dict:
    return json.loads(frozen)
