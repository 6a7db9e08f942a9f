"""Per-layer metric readers, one module per metric, found by the metric's
name. Each exposes ``read(ctx) -> float | None``; None means the run held
nothing to read and the metric is left out of the result line.

``ctx`` keys: ``trace`` (``bench.trace.reduce`` of the traced window with
``bench.scopes.reduce``'s ``scopes`` and ``idle_spans`` added, or None),
``spans`` (the flight recorder's events of the traced run),
``window_calls`` (calls of the window), ``cell``, ``shape``
(``bench.cells.shape``) and ``peaks`` (``bench.costs.peaks`` of the device).
"""
