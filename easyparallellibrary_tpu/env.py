"""Process-global environment singleton.

TPU-native analog of the reference's ``epl/env.py`` (``Env.get`` :43-51,
``Env.init`` :111-127): owns the active :class:`Config`, the
:class:`Cluster` (device mesh), the strategy context recorded by
``replicate``/``split`` scopes, and the metric-merge collections.

Unlike the reference there is no TF server to start and no monkey-patching
to install — ``init`` simply wires the functional pieces together.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from easyparallellibrary_tpu.config import Config


class Env:
  """Singleton context for one training program."""

  _instance: Optional["Env"] = None

  def __init__(self):
    self.config: Config = Config()
    self.cluster = None            # set by epl.init()
    self.strategy_context = None   # set by init/reset
    # Metric-merge collections (reference: epl/ir/graph.py:40-64,600-649).
    self.collections: Dict[str, List[Any]] = {}
    # Free-form per-run info (reference: Env.parallel_information).
    self.parallel_information: Dict[str, Any] = {}
    self._reset_strategy_context()

  def _reset_strategy_context(self):
    # Imported lazily to avoid an import cycle (strategies import Env).
    from easyparallellibrary_tpu.strategies.context import StrategyContext
    self.strategy_context = StrategyContext()

  @classmethod
  def get(cls) -> "Env":
    if cls._instance is None:
      cls._instance = Env()
    return cls._instance

  def reset(self, config: Optional[Config] = None):
    """Drop all recorded state (reference: Env.reset, epl/env.py:66-72)."""
    self.config = config if config is not None else Config()
    self.cluster = None
    self.collections = {}
    self.parallel_information = {}
    self._reset_strategy_context()

  def init(self, config: Optional[Config] = None):
    self.reset(config)
    return self

  def mesh_built(self) -> bool:
    """Whether a multi-device mesh has been built: what a kernel's entry
    called with ``impl=None`` takes its operands as spread over (the
    legacy ``generate()`` decode, a decoder called bare); the serving
    engine resolves each lowering from its own mesh and passes it."""
    mesh = self.cluster.built_mesh if self.cluster is not None else None
    return mesh is not None and mesh.size > 1

  # -- collections ---------------------------------------------------------

  def add_to_collection(self, value, key: str):
    self.collections.setdefault(key, []).append(value)

  def get_collection(self, key: str) -> List[Any]:
    return list(self.collections.get(key, []))
