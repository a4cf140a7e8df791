"""``"kind": "setup_span"``: one of the program's set-up spans (``setup.*``,
which ``train()`` records once, when its first training dispatch returns):
the mean of the named span ``span`` over those that ended before the window
opened, divided by ``divisor`` (1000 turns the milliseconds of
``span_mean_ms`` into seconds).  Nothing is read — and the metric is left
out — where the program records no such span, or where it ended inside the
window."""


def read(spec, ctx):
    return ctx.span_mean_ms(spec["span"], float("-inf"), ctx.t_open,
                            spec.get("divisor", 1.0))
