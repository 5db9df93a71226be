"""Model FLOPs of one training step, from a configuration's sizes.

Forward plus backward, counted as three forward passes; recomputation
(rematerialisation) is not counted.  A matrix product of (m, k) by (k, n)
is 2mkn.  Elementwise work, norms and the softmax are not counted.

- A layer is counted by its family's file, ``bench/flops/<name>.py``,
  found under the cell's root: ``layer_flops(m, seq)`` gives one layer's
  forward FLOPs per token, and ``num_layers`` of them make the stack (a
  family whose layers differ gives their mean).  The name is the
  configuration's model's ``family``.
- The vocabulary head counts the real vocabulary, not its padding.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, Dict

from benchlib.spec import ROOT, load_module

LayerFlops = Callable[[Dict[str, Any], int], float]


def family(config: Dict[str, Any], root: Path = ROOT) -> LayerFlops:
    """The ``layer_flops`` of a configuration's family file.  A family
    without a file is an error that names the file to add; no other
    family's count stands in for it."""
    name = config["model"]["family"]
    path = Path(root) / "bench" / "flops" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no FLOP count for the layers of the family {name!r}: add "
            f"bench/flops/{name}.py with layer_flops(m, seq)")
    return load_module(path).layer_flops


def forward_flops_per_token(m, seq: int, layer_flops: LayerFlops) -> float:
    layer = layer_flops(m, seq)
    head = 2 * m["d_model"] * m["vocab_size"]
    return m["num_layers"] * layer + head


def train_step_flops(m, batch: int, seq: int,
                     layer_flops: LayerFlops) -> float:
    return 3.0 * forward_flops_per_token(m, seq, layer_flops) * batch * seq
