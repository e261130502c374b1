"""One reader per metric, ``<metric>.py``, each with ``read(run)``: the value
in the metric's unit, or ``None`` where the run holds nothing to read.

``run`` has ``window`` (:class:`bench.systems.common.Window`), ``spans``
(harness spans), ``compiles``, ``trace`` (the reduced trace of a traced run,
else ``None``), ``events`` (its events), ``setup_s``, ``pool_bytes``,
``config``, ``mix``, ``shape`` (a dense model's sizes, else ``None``) and
``peaks`` (the chip's published peaks)."""
