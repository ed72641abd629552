"""Cells of the benchmark cut down so that a CPU test run can hold them."""

from __future__ import annotations

import copy
import dataclasses

from benchmarks.chip import harness, shapes

TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512}


def cut(name: str, sizes: dict | None = None, **mix) -> harness.Cell:
    """Cell ``name`` with the configuration's sizes and the mix's entries
    replaced."""
    cell = harness.load_cell(name)
    config = {**cell.config, **(TINY if sizes is None else sizes)}
    new_mix = copy.deepcopy(cell.mix)
    for key, value in mix.items():
        if isinstance(value, dict):
            new_mix[key] = {**new_mix[key], **value}
        else:
            new_mix[key] = value
    return dataclasses.replace(cell, config=config,
                               sizes=shapes.Sizes.from_config(config),
                               mix=new_mix)
