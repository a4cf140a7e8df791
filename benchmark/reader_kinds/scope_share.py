"""``"kind": "scope_share"``: the share of chip 0's busy time that the
operations under one of the program's ``jax.named_scope`` names took in the
traced slice, forward and backward together (``xplane.scope_split``).

``scope``: the scope's name.  Nothing is read — and the metric is left out —
where the slice's operations carry no path at all (no ``xplane_pb2`` here,
or programs from a compile cache older than the scopes) or none of them
lies under the scope."""
from benchmark import xplane


def read(spec, ctx):
    events = ctx.device_ops()
    if "scope_split" not in ctx.cache:      # one pass for every scope
        ctx.cache["scope_split"] = (
            xplane.scope_split(events)
            if any("path" in ev for ev in events) else {})
    shares = ctx.cache["scope_split"]
    found = [shares[key] for key in (spec["scope"], spec["scope"] + ".bwd")
             if key in shares]
    return sum(found) if found else None
