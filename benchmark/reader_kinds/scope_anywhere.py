"""``"kind": "scope_anywhere"``: the share of chip 0's busy time that the
operations with one of the given ``jax.named_scope`` names ANYWHERE on their
``op_name`` path took in the traced slice — every pass together: the online
network's forward and backward, the target network's forward, acting.

``scope_share`` gives an operation to the outermost scope of its path, which
for a part of the memory core is ``core``, ``target_forward`` or ``act``;
this kind reads the parts inside them.  ``scopes``: the names (an operation
counts once, whichever of them it lies under).  Nothing is read — and the
metric is left out — where the slice's operations carry no path at all or
none of them lies under any of the names, as in a program without these
scopes."""
import re

from benchmark import xplane

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")    # jvp(x), transpose(jvp(x)), ...


def components(path):
    """The scope names on an ``op_name`` path, wrappers such as ``jvp(x)``
    and ``transpose(jvp(x))`` taken off."""
    out = []
    for part in path.split(":")[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(2)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def read(spec, ctx):
    if "self_ns_by_path" not in ctx.cache:      # one pass for every metric
        by_path = {}
        events = ctx.device_ops()
        for ev, ns in xplane.self_times(events):
            path = ev.get("path")
            if path:
                by_path[path] = by_path.get(path, 0.0) + ns
        ctx.cache["self_ns_by_path"] = (by_path,
                                        1e9 * xplane.busy_seconds(events))
    by_path, busy_ns = ctx.cache["self_ns_by_path"]
    wanted = set(spec["scopes"])
    found = [ns for path, ns in by_path.items()
             if wanted.intersection(components(path))]
    if not found or busy_ns <= 0:
        return None
    return 100.0 * sum(found) / busy_ns
