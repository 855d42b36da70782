"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax device query, and smoke tests must keep seeing 1 real device.

Mesh axes
---------
single-pod : (16, 16)        → ("data", "model")      — 256 chips (one v5e pod)
multi-pod  : (2, 16, 16)     → ("pod", "data", "model") — 512 chips, 2 pods

* LM training: FSDP/DP over ("pod","data"), TP/EP over "model".
* LM serving:  batch over ("pod","data"), TP over "model"; long-context decode
  additionally shards KV over "data" (split-K attention).
* CMA-ES strategies: the evaluation axis is the whole mesh flattened
  (K-Distributed heap layout over pod→data→model order); K-Replicated phases
  re-view the same devices as ("grp", "mem") via ``make_group_mesh``.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def _mk(shape, names, devices=None):
    kw = {"axis_types": (jax.sharding.AxisType.Auto,) * len(names)}
    if devices is None:
        return jax.make_mesh(shape, names, **kw)
    devs = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(devs, names, **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh_for(n_devices: Optional[int] = None, model_parallel: int = 1,
                  pods: int = 1):
    """A (pod, data, model)-shaped mesh for an arbitrary device count
    (elastic scaling: checkpoint resharding accepts any such mesh)."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n % (model_parallel * pods):
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}×pods={pods}")
    data = n // (model_parallel * pods)
    if pods > 1:
        return _mk((pods, data, model_parallel), ("pod", "data", "model"))
    return _mk((data, model_parallel), ("data", "model"))


def make_eval_mesh(n_devices: Optional[int] = None):
    """1-D mesh over all devices — the CMA-ES evaluation axis."""
    n = n_devices if n_devices is not None else len(jax.devices())
    return _mk((n,), ("ev",))


def make_campaign_mesh(n_devices: Optional[int] = None, devices=None):
    """1-D ("camp",) mesh — the campaign-batch axis of the mesh campaign
    engine (distributed/mesh_engine.py): (fid, instance, run) members shard
    over it, one slice per device/island.  ``devices`` carves the mesh out of
    an explicit device list (scaling curves over prefixes of the virtual-CPU
    fleet; re-viewing a production mesh's devices as one flat campaign axis).
    """
    if devices is not None:
        devices = list(devices)
        return _mk((len(devices),), ("camp",), devices=devices)
    n = n_devices if n_devices is not None else len(jax.devices())
    return _mk((n,), ("camp",))


def make_group_mesh(n_groups: int, group_size: int):
    """(grp, mem) view for one K-Replicated phase."""
    return _mk((n_groups, group_size), ("grp", "mem"))
