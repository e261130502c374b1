"""Plain references of the model families that configurations name.

A configuration's ``model`` names its family's module,
``bench/models/<model>.py``, which gives the harness everything that is
specific to the family:

* ``shape_of(config)``: the model's sizes and constants, with ``vocab_size``,
  ``params()``, ``token_flops(context, logits)``, ``prefill_flops(n)`` and
  ``decode_step_bytes(contexts, param_dtype, kv_dtype)``;
* ``arch(config, shape)``: the program's ``ArchConfig``;
* ``make_weights(shape, seed, dtype)``: weights in the program's layout, made
  on the device from the seed;
* ``forward_hidden(w, tokens, shape, mode)`` and ``logits(w, hidden, mode)``:
  the reference, in ``mode`` ``"float32"`` or ``"bfloat16"``;
* ``quantize(w, kind)``: the weights rounded to ``int8`` or
  ``float8_e4m3fn``, for the controls;
* ``tiny(config)``: the configuration at a size a CPU test run can hold.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType
from typing import Dict

HERE = Path(__file__).resolve().parent


def module_of(config: Dict) -> ModuleType:
    """The family module that ``config["model"]`` names."""
    name = config["model"]
    path = HERE / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier() and path.is_file()):
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names model {name!r}: "
            f"no file bench/models/{name}.py")
    return importlib.import_module(f"{__name__}.{name}")
