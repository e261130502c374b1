"""One reader per metric, ``<metric>.py``, each with ``read(run)``: the value
in the metric's unit, or ``None`` where the run holds nothing to read.

``run`` has ``window`` (:class:`bench.systems.common.Window`), ``spans``
(harness spans), ``compiles``, ``trace`` (the reduced trace of a traced run,
else ``None``), ``events`` (its events: device operations, harness spans and
the program's ``hotswap.`` spans with their stats), ``setup_s``,
``pool_bytes``, ``config``, ``mix``, ``shape`` (the model's sizes and counts
from its family module's ``shape_of``, see :mod:`bench.models`; ``None`` for
a configuration that names no model) and ``peaks`` (the chip's published
peaks)."""
