"""Formulas that came as files: ``<formula>.py`` exports ``read(spec, ctx)``
like a reader kind; ``layer_metrics/<name>.json`` names it under
``"kind": "formula", "formula": "<formula>"``."""
