"""The architectures the benchmark runs, one module each, named by a
configuration file's ``"arch"``.  A module gives:

- ``STREAMS``: layer tensor name -> random stream id (10 or more);
- ``groups(m, slots, rank)``: the stack as an ordered list of layer groups
  ``{"name", "n", "shapes", "lora"}`` (layer count, tensor shapes, LoRA
  bank shapes);
- ``to_program(sem)``: ``(params, lora)`` of the stack in the program's
  layout, from ``weights.build``'s ``sem["groups"]``;
- ``layer(m, group, w, x, ids, fp8)``: the float32 forward pass of one
  layer of the group named ``group`` over one row's sequence x (P, d);
  ``row_block(m, batch)``: the reference's rows per block;
- ``decode_step_flops(m, rank, rows, pos)`` and ``lora_calls(m)`` ->
  ``[(target, n_layers, out_width)]``, for the readers.
"""
from __future__ import annotations

import importlib
from pathlib import Path


def load(cfg: dict):
    """The module that configuration ``cfg`` names."""
    name = cfg.get("arch")
    if not (isinstance(name, str) and name.isidentifier()
            and (Path(__file__).parent / f"{name}.py").is_file()):
        raise ValueError(f"configuration {cfg.get('name')!r} names arch "
                         f"{name!r}: no bench/arch/{name}.py")
    return importlib.import_module(f"{__name__}.{name}")
