"""Reader kinds that came as files: ``<kind>.py`` exports
``read(spec, ctx)`` (``spec`` is the metric's ``layer_metrics/<name>.json``,
``ctx`` a ``readers.ReadContext``) and returns a number, or None where it
finds nothing to read."""
