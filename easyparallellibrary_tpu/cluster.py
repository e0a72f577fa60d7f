"""Cluster: device enumeration, layouts, and mesh construction.

TPU-native analog of the reference's ``epl/cluster.py``: instead of parsing
``TF_CONFIG`` and slicing a GPU grid into per-taskgraph ``VirtualDevice``
lists (reference :36-100, :133-143), we enumerate ``jax.devices()`` and build
a single named :class:`jax.sharding.Mesh` over the logical axes
``(stage, data, seq, expert, model)``.  Pipeline stages are a mesh axis, not
separate device groups — XLA partitions one program over the whole mesh.

Layout policies mirror the reference's (``AllLayout`` :108, ``AutoLayout``
:146, ``SpecificLayout`` :162, ``AwareRowLayout`` :169):

  * ``auto``     — data-parallel size inferred as
                   total_devices / (stage*model*seq*expert), the analog of
                   replicas = total / Σ per-stage device_count
                   (reference epl/cluster.py:150-159).
  * ``all``      — everything on one data axis (pure DP).
  * ``specific`` — user-provided mesh shape (``cluster.mesh_shape`` config).
  * topology awareness (the ``AwareRowLayout`` role) comes from
    ``jax.experimental.mesh_utils.create_device_mesh``, which orders TPU
    devices so the innermost axes ride the shortest ICI hops.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.env import Env


class VirtualDevice:
  """The devices backing one taskgraph / pipeline stage.

  Parity object for the reference's ``VirtualDevice``
  (epl/cluster.py:36-100); in this framework it is introspection metadata —
  placement is done by XLA from the mesh, not by assigning device strings.
  """

  def __init__(self, stage_index: int, devices: Sequence[jax.Device]):
    self.stage_index = stage_index
    self.devices = list(devices)

  @property
  def num_devices(self) -> int:
    return len(self.devices)

  def __repr__(self):
    return (f"VirtualDevice(stage={self.stage_index}, "
            f"devices={[getattr(d, 'id', d) for d in self.devices]})")


def _build_device_array(devices: List[jax.Device],
                        shape: Sequence[int],
                        prefer_intra_node: bool) -> np.ndarray:
  """Arrange devices into a mesh-shaped ndarray.

  On TPU, delegate to ``mesh_utils.create_device_mesh`` for ICI-topology-aware
  placement (the reference's AwareRowLayout host-reordering role,
  epl/cluster.py:193-241); a shape it cannot place raises.  On CPU/virtual
  platforms the layout is a row-major reshape; with ``prefer_intra_node``
  the innermost axes vary fastest within a process, mirroring
  ``device_place_prefer_intra_node`` (epl/cluster.py:137).
  """
  shape = tuple(shape)
  n = math.prod(shape)
  if n != len(devices):
    raise ValueError(f"Mesh shape {shape} needs {n} devices, "
                     f"have {len(devices)}")
  platform = devices[0].platform if devices else "cpu"
  if platform == "tpu" and n > 1:
    from jax.experimental import mesh_utils
    return mesh_utils.create_device_mesh(shape, devices=devices)
  order = sorted(devices, key=lambda d: (d.process_index, d.id)) \
      if prefer_intra_node else list(devices)
  return np.array(order, dtype=object).reshape(shape)


class Layout:
  """Base layout: computes the per-axis mesh sizes (reference Layout :244)."""

  name = "base"

  def axis_sizes(self, cluster: "Cluster",
                 requested: Dict[str, int]) -> Dict[str, int]:
    raise NotImplementedError


class AllLayout(Layout):
  """All devices on the data axis — pure DP (reference AllLayout :108)."""

  name = "all"

  def axis_sizes(self, cluster, requested):
    sizes = {axis: 1 for axis in constants.MESH_AXES}
    sizes[constants.DATA_AXIS] = cluster.num_devices
    return sizes


class AutoLayout(Layout):
  """Infer data-parallel size from leftover devices.

  Reference: replicas = total_devices / Σ per-stage device_count
  (epl/cluster.py:150-159).  Here: data = total / (stage*seq*expert*model).
  """

  name = "auto"

  def axis_sizes(self, cluster, requested):
    sizes = {axis: int(requested.get(axis, 1)) for axis in constants.MESH_AXES}
    fixed = math.prod(
        sizes[a] for a in constants.MESH_AXES if a != constants.DATA_AXIS)
    total = cluster.num_devices
    if total % fixed != 0:
      raise ValueError(
          f"Cannot lay out mesh: {total} devices not divisible by "
          f"stage*seq*expert*model = {fixed} "
          f"(requested {requested})")
    inferred = total // fixed
    explicit = requested.get(constants.DATA_AXIS, 0)
    sizes[constants.DATA_AXIS] = explicit if explicit else inferred
    if math.prod(sizes.values()) != total:
      raise ValueError(
          f"Mesh sizes {sizes} do not cover {total} devices")
    return sizes


class SpecificLayout(Layout):
  """Exact user-provided shape (reference SpecificLayout :162).

  Parsed from ``cluster.mesh_shape`` config, e.g. ``"stage:2,data:2,model:2"``.
  """

  name = "specific"

  def __init__(self, spec: str):
    self.sizes = {axis: 1 for axis in constants.MESH_AXES}
    for part in spec.split(","):
      if not part.strip():
        continue
      axis, _, num = part.partition(":")
      axis = axis.strip()
      if axis not in self.sizes:
        raise ValueError(f"Unknown mesh axis '{axis}' in mesh_shape spec "
                         f"{spec!r}; valid: {constants.MESH_AXES}")
      self.sizes[axis] = int(num)

  def axis_sizes(self, cluster, requested):
    if math.prod(self.sizes.values()) != cluster.num_devices:
      raise ValueError(
          f"mesh_shape {self.sizes} does not match device count "
          f"{cluster.num_devices}")
    for axis, size in requested.items():
      if size > 1 and self.sizes.get(axis, 1) != size:
        raise ValueError(
            f"cluster.mesh_shape sets {axis}={self.sizes.get(axis, 1)} but "
            f"the recorded strategy scopes require {axis}={size}; make the "
            f"explicit shape consistent with the annotations")
    return dict(self.sizes)


_LAYOUTS = {"all": AllLayout, "auto": AutoLayout}


class Cluster:
  """Device pool + mesh factory (reference Cluster, epl/cluster.py:293).

  The reference parses TF_CONFIG and starts a TF server; here multi-host
  bootstrap is `jax.distributed.initialize` (done by the launcher CLI) and
  the global device list already spans all hosts.
  """

  def __init__(self,
               devices: Optional[List[jax.Device]] = None,
               layout: str | Layout = "auto"):
    self.devices = list(devices) if devices is not None else jax.devices()
    self.process_index = getattr(jax, "process_index", lambda: 0)()
    self.process_count = getattr(jax, "process_count", lambda: 1)()
    config = Env.get().config
    spec = config.cluster.mesh_shape
    if spec:
      self.layout: Layout = SpecificLayout(spec)
    elif isinstance(layout, Layout):
      self.layout = layout
    else:
      self.layout = _LAYOUTS[layout]()
    self._mesh: Optional[Mesh] = None
    self.virtual_devices: List[VirtualDevice] = []

  @property
  def num_devices(self) -> int:
    return len(self.devices)

  @property
  def devices_per_process(self) -> int:
    return max(1, self.num_devices // max(1, self.process_count))

  def build_mesh(self, **requested: int) -> Mesh:
    """Build the 5-axis mesh; size-1 axes are free.

    ``requested`` gives sizes for non-data axes (e.g. ``stage=2, model=4``);
    the layout infers the rest.
    """
    sizes = self.layout.axis_sizes(self, requested)
    shape = tuple(sizes[a] for a in constants.MESH_AXES)
    prefer_intra = Env.get().config.cluster.device_place_prefer_intra_node
    dev_array = _build_device_array(self.devices, shape, prefer_intra)
    self._mesh = Mesh(dev_array, constants.MESH_AXES)
    # Per-stage virtual devices for introspection/parity.
    num_stages = sizes[constants.STAGE_AXIS]
    self.virtual_devices = [
        VirtualDevice(i, dev_array[i].reshape(-1).tolist())
        for i in range(num_stages)
    ]
    return self._mesh

  @property
  def mesh(self) -> Mesh:
    if self._mesh is None:
      self.build_mesh()
    return self._mesh

  @property
  def built_mesh(self) -> Optional[Mesh]:
    """The mesh if :meth:`build_mesh` has run, else None — the
    observe-without-forcing accessor (``mesh`` force-builds) for
    components that only want to ADOPT an existing cluster layout,
    e.g. the serving engine's ambient-mesh resolution."""
    return self._mesh

  def axis_size(self, axis: str) -> int:
    return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[axis]

  def __repr__(self):
    shape = None if self._mesh is None else dict(
        zip(self._mesh.axis_names, self._mesh.devices.shape))
    return (f"Cluster(num_devices={self.num_devices}, "
            f"processes={self.process_count}, layout={self.layout.name!r}, "
            f"mesh={shape})")
