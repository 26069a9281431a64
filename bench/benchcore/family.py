"""Model families: what the harness knows of a model's structure lives in
one file per family, `families/<family>.py`, found by the name that a
configuration file gives under `"family"` (`spec.load_family`). A
configuration of a new family is added by new files alone; nothing here
changes.

A family module provides:

  program_config(config) -> (cfg, want)
      the program's `ModelConfig` for the configuration file (the preset
      `config["arch"]`, with every key the file lists in `reduced`
      applied through `cfg.replace`; `preset` below does both), and the
      {attribute: number} that the file states, which the driver checks
      against `cfg`;
  layout(config) -> {path: (shape, dtype name, kind)}
      every leaf of the weights, in the order that seeds them
      (`weights.py`: leaf i draws from `fold_in(base, i)`); a kind is
      "embed", "norm", "altup_p", "altup_g" or a matmul's fan-in;
  arch(config) -> tuple of (key, value)
      the hashable items the reference reads; the generic part
      (`reference.final_hidden`) reads "K", "recycled", "d_model",
      "vocab" and "rms_norm_eps" (the final norm's);
  segments(params, arch) -> [(segment params, n layers, layer fn), ...]
      the stack in order; each segment's leaves are stacked on a leading
      layer axis, which also holds AltUp's "altup_p" and "altup_g", and
      `layer fn(x, p, arch, mm)` computes one width-d layer with its
      residuals in float32, with every matmul through `mm`
      (`reference._Math`), so that the fp8 control applies to it;
  shape(config) -> models.Shape
      what the cost models read: per layer, the matmul weights a token
      multiplies through (for an expert layer: the active and shared
      experts and the router), the attention FLOPs per key, the K/V
      elements of a cached row and its window.

What every family shares stays in `benchcore`: the embedding and AltUp's
widening or recycling, the layer index across segments, the final norm,
a tied `embed` or an untied `lm_head`, the blockwise comparisons and the
control (`reference.py`), the weights' values (`weights.py`), and the
FLOP and byte models over a `Shape` (`models.py`).
"""
from __future__ import annotations

from typing import Dict


def preset(config: dict, reducible: Dict[str, str]):
    """The program's preset for `config["arch"]`, widened by AltUp's K,
    with each key of the file's `reduced` set to the file's number:
    `reducible` maps such a key of `config["model"]` to the `ModelConfig`
    attribute it sets. A reduced key the family cannot cut raises."""
    from repro.configs import get_config
    m = config["model"]
    cfg = get_config(config["arch"], smoke=bool(config.get("smoke")),
                     altup_k=int(config["altup"]["K"]))
    unknown = [k for k in config.get("reduced", []) if k not in reducible]
    if unknown:
        raise KeyError(f"reduced keys {unknown} are not among the keys "
                       f"this family can cut: {sorted(reducible)}")
    return cfg.replace(**{reducible[k]: m[k]
                          for k in config.get("reduced", [])})
