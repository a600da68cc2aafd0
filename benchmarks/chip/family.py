"""A configuration's model code, found by its ``model.family`` (the key the
program dispatches on) in ``families/<family>.py``.  A family the harness
does not know yet is added as one new file there.

A family module gives:

- ``layout(c)``: every parameter leaf, ``{dotted.name: Leaf}``, named as in
  the program's parameter tree;
- ``layer_leaves(c, i)``: the leaves of decoder layer ``i``, as
  ``{name in the layer: (leaf name, index in its stack or None)}``;
- ``block(x, w, c, quant)``: that layer of the plain reference over
  x: (b, s, d) float32, its weights ``w`` named as ``layer_leaves`` names
  them; every matrix product at ``Precision.HIGHEST``, and with ``quant``
  the fp8 control (``reference._mm``, ``_q8``);
- ``matmul_params(c)``: the weights multiplied per token, active ones only;
- ``attn_flops_per_position(c)``: decode attention FLOPs per live position,
  over every layer;
- ``kv_bytes_per_position(c)`` and ``row_bytes(c)``: the bytes decode
  attention must move per live position, and per live row (its query,
  output and length), over every layer;
- ``reference_row_bytes(c, pad_to)``: the bytes one row of ``pad_to``
  tokens holds in the reference's largest layer buffers.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
from types import ModuleType
from typing import Dict, NamedTuple, Tuple

DIR = pathlib.Path(__file__).resolve().parent / "families"


class Leaf(NamedTuple):
    shape: Tuple[int, ...]   # of one layer, when stacked
    served: bool             # stored in the served dtype (else float32)
    init: str                # weights._value: embed | norm | bias | fan_in
    stack: int               # layers stacked on a leading axis; 0: unstacked


def of(c: Dict) -> ModuleType:
    """The family module of configuration fields ``c``; a missing
    ``family`` is ``dense``, as the program's ``ModelConfig`` has it."""
    return load(c.get("family", "dense"))


def load(name: str) -> ModuleType:
    path = DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"model family {name!r} has no module: expected {path}")
    return _load(str(path))


@functools.lru_cache(maxsize=None)
def _load(path: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "chip_family_" + pathlib.Path(path).stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
