"""The sizes of a dense GQA decoder, from a configuration's file (never
from the program), under the source's own key names."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

_KEYS = {"layers": "num_hidden_layers", "d_model": "hidden_size",
         "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
         "head_dim": "head_dim", "d_ff": "intermediate_size",
         "vocab": "vocab_size"}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int


def dims(config: Dict[str, Any]) -> Dims:
    """``config`` is a configuration file: its ``config`` block holds
    the source's keys."""
    c = config["config"]
    got = {name: int(c[k]) for name, k in _KEYS.items() if k in c}
    got.setdefault("head_dim", got["d_model"] // got["heads"])
    return Dims(**got)
