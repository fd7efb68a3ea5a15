"""Utility subpackage. Deliberately lazy: no eager submodule imports.

``debug`` and ``profiling`` import jax at module level; eagerly pulling
them in here would make every stdlib-only utility (``xplane``,
``logging``) drag the full jax import into host-side tools like
``cli trace-summary``. ``from ...utils import debug`` still works:
the import system falls back to importing the submodule when the
attribute is absent.
"""

from replication_faster_rcnn_tpu.utils.logging import MetricLogger  # noqa: F401
