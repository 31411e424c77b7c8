"""spread-1m-pct5's own guarantee, beside the base replay's twelve: in the
order the client's watch shows the binds, no bind leaves its Deployment's
pod count in the bound node's zone more than ``maxSkew`` above that
Deployment's least-populated zone (PodTopologySpread, whenUnsatisfiable:
DoNotSchedule, at every bind: upstream's filter, not a wave's).

Imports nothing: what it is given is all it has.  Pod ``i`` has the shape
``pattern[i % len(pattern)]``; a shape's ``app`` is its Deployment (the
label every constraint of the mix selects), its ``spread_constraints`` the
raw constraints; node ``i`` lies in zone ``i % nodes["zones"]``, and every
zone holds nodes, so the minimum is over them all.
"""

ZONE_KEY = "topology.kubernetes.io/zone"


def hard_zone_constraints(shape):
    """``(selected app, maxSkew)`` of the shape's zone constraints that
    forbid (anything but ScheduleAnyway does)."""
    return [
        (c["labelSelector"]["matchLabels"]["app"], int(c.get("maxSkew", 1)))
        for c in shape.get("spread_constraints") or ()
        if c["topologyKey"] == ZONE_KEY
        and c.get("whenUnsatisfiable") != "ScheduleAnyway"
    ]


def numbers(seen, replayed, *, nodes, pattern, offered):
    zones = int(nodes["zones"])
    apps = [shape.get("app") for shape in pattern]
    held = [hard_zone_constraints(shape) for shape in pattern]
    count = {app: [0] * zones for app in apps}
    for selected, _skew in (c for cs in held for c in cs):
        count.setdefault(selected, [0] * zones)
    exceeded = 0
    period = len(pattern)
    for pod, node in zip(seen["bind_pod"].tolist(), seen["bind_node"].tolist()):
        if node < 0:
            continue                    # the base replay's unknown_node
        shape, zone = pod % period, node % zones
        count[apps[shape]][zone] += 1
        for selected, skew in held[shape]:
            in_zone = count[selected]
            exceeded += in_zone[zone] - min(in_zone) > skew
    return {"zone_skew_exceeded": int(exceeded)}
