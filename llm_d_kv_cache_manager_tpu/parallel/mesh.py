"""Device-mesh construction for the TPU fleet.

Axes convention (scaling-book style):

- ``dp``   — data parallel, across hosts/slices (DCN or ICI);
- ``tp``   — tensor parallel, within a slice (ICI): attention heads and MLP
             width sharded, XLA inserts all-gather/reduce-scatter.

The serving engine uses a ``tp``-only mesh per replica (one replica = one
scored "pod"); training composes ``dp × tp``. The reference has no
in-process parallelism at all (SURVEY §2.3) — its TP was a vLLM flag; here
the equivalent machinery is in-tree.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh


@dataclass
class MeshConfig:
    dp: int = 1
    tp: int = 1
    #: sequence-parallel degree (ring attention over the "sp" axis for
    #: long-context prefill; see parallel/ring_attention.py). Placed
    #: between dp and tp so ring neighbors are ICI-adjacent within a
    #: dp replica.
    sp: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.sp * self.tp


def make_mesh(config: Optional[MeshConfig] = None, devices=None) -> Mesh:
    """Build a (dp, sp, tp) mesh over the given devices (default: all)."""
    cfg = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    if len(devices) < cfg.n_devices:
        raise ValueError(
            f"mesh needs {cfg.n_devices} devices (dp={cfg.dp} × sp={cfg.sp} "
            f"× tp={cfg.tp}), have {len(devices)}"
        )
    grid = np.asarray(devices[: cfg.n_devices]).reshape(cfg.dp, cfg.sp, cfg.tp)
    return Mesh(grid, axis_names=("dp", "sp", "tp"))
