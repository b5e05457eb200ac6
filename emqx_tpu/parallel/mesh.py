"""Device mesh construction for the broker.

Two mesh axes, mirroring the reference's two scale dimensions
(SURVEY §5 "long-context"):

  - ``data``: publish-batch sharding — the analogue of EMQX's hashed
    broker/router worker pools (each worker handles a slice of
    traffic, src/emqx_broker.erl:428-429);
  - ``trie``: subscription-table sharding — the analogue of topic
    shards + replicated Mnesia tables (src/emqx_broker_helper.erl:
    82-92, src/emqx_router.erl:77-86): each chip holds a slice of the
    filter set and match results are all-gathered over ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(n_data: int, n_trie: int,
              devices: Optional[Sequence] = None) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    need = n_data * n_trie
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    grid = np.array(devs[:need]).reshape(n_data, n_trie)
    return Mesh(grid, ("data", "trie"))


def default_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Prefer sharding the batch; put leftover factor on the trie axis.

    For n a power of two: (n, 1) for n ≤ 2 else (n // 2, 2) — both
    axes exercised whenever possible.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    if n <= 2:
        return make_mesh(n, 1)
    return make_mesh(n // 2, 2)


def mesh_axes(mesh) -> Optional[dict]:
    """``{"data": d, "trie": t}`` of a mesh in either form (the
    ``[matcher] mesh`` table of a file, or the placed
    :class:`~jax.sharding.Mesh` a node runs on); ``None`` for no
    mesh and for 1 x 1, which is one chip."""
    if mesh is None:
        return None
    shape = mesh if isinstance(mesh, dict) else mesh.shape
    axes = {"data": int(shape.get("data", 1)),
            "trie": int(shape.get("trie", 1))}
    return None if axes == {"data": 1, "trie": 1} else axes
