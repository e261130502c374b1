"""Drivers of the system under test, one module per configuration ``system``.

Each module has a ``Cell`` with ``setup()``, ``run_window(seconds)``,
``pool_bytes()``, ``release()`` and ``check(limits, control)``; see
:mod:`bench.systems.common` for what a window records.
"""
