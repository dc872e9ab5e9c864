"""Mesh topology for the GAL runtime and the LM serving arc.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax import; smoke tests see 1 device).

Two families of meshes live here:

* ``make_device_mesh`` — the generic dense-axis constructor used by the LM
  serving/training arc (data/model/pod axes).  ``production_mesh_spec``
  captures the TPU v5e target shapes that used to be hard-coded in the
  removed ``make_production_mesh``/``make_test_mesh`` seed constructors.
* ``make_org_mesh`` — the GAL protocol mesh: an "org" axis carrying the
  stacked organizations (optionally a block of several orgs per device) and
  an optional "data" axis sharding each org's N rows.
"""
from __future__ import annotations

import jax


def production_mesh_spec(*, multi_pod: bool = False) -> tuple:
    """(shape, axes) of the TPU v5e production target.

    Single pod: (16, 16) over ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) over ("pod", "data", "model") = 512 chips."""
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_device_mesh(shape, axes):
    """Dense named device mesh over the first prod(shape) local devices.

    The one documented constructor for LM-arc meshes (serving, training,
    dry-run): pass ``production_mesh_spec()`` for the deployment target or a
    small shape like ``(2, 4)`` over ``("data", "model")`` for CPU sharding
    tests (requires >= prod(shape) local devices). Axes are ``Auto``: the
    model code places activations through sharding constraints and lets
    GSPMD propagate the rest, which explicit axes would refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def org_mesh_eligible(m: int, data_shards: int = 1) -> bool:
    """True when an M-organization "org" mesh can be built on this host.

    Two placements are supported (d_org = device_count // data_shards is the
    size of the "org" axis):

    * one-to-one — ``M <= d_org`` and ``d_org % M == 0``: every org gets its
      own device (the paper's physically-separate compute sites).
    * block — ``M > d_org`` and ``M % d_org == 0``: the stacked org axis is
      block-sharded, a contiguous block of ``M // d_org`` orgs per device,
      so e.g. M=64 runs on 8 devices.

    ``data_shards`` > 1 additionally requires the device count to factor as
    d_org * data_shards.  Single-device hosts and M=1 are never eligible —
    the collectives would be pure overhead there."""
    d = len(jax.devices())
    if m <= 1 or d <= 1 or data_shards < 1 or d % data_shards != 0:
        return False
    d_org = d // data_shards
    if d_org < 1:
        return False
    if m <= d_org:
        return d_org % m == 0
    return m % d_org == 0


def org_block_size(m: int, data_shards: int = 1) -> int:
    """Orgs per device along the "org" axis (1 under one-to-one placement)."""
    d_org = len(jax.devices()) // data_shards
    return 1 if m <= d_org else m // d_org


def grouped_mesh_eligible(group_sizes) -> bool:
    """True when every planner group's org stack can shard its org axis
    across ALL local devices: multi-device host and the device count divides
    each group size. The grouped GAL engine then places one org-shard of
    every group per device — heterogeneous groups stay separate programs,
    each partitioned over the same "org" mesh (GSPMD), which is how a
    mixed-model org set on a matching device count maps onto the mesh."""
    d = len(jax.devices())
    return (d > 1 and bool(group_sizes)
            and all(s % d == 0 for s in group_sizes))


def make_org_mesh(m: int, data_shards: int = 1):
    """Mesh mapping organization blocks -> devices along an "org" axis.

    One-to-one placement uses the first M local devices, one organization
    each; block placement uses all d_org devices, a contiguous block of
    ``org_block_size(m)`` orgs per device.  With ``data_shards`` > 1 the
    mesh gains a second "data" axis that shards each org's N rows.  Callers
    gate on ``org_mesh_eligible``.  The org-sharded GAL engine places each
    org's vertical slice and per-round params along "org" and runs Alg. 1's
    residual broadcast / fitted-value gather as real collectives over this
    axis."""
    import numpy as np
    d_org = len(jax.devices()) // data_shards
    use = min(m, d_org)
    devs = np.asarray(jax.devices()[: use * data_shards])
    if data_shards == 1:
        return jax.sharding.Mesh(devs, ("org",))
    return jax.sharding.Mesh(devs.reshape(use, data_shards), ("org", "data"))


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a mesh (pod extends data across pods)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size
