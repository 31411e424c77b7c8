"""affinity-100k's own guarantee, beside the base replay's twelve: no pod is
bound to a node whose labels fail its ``nodeSelector`` or every one of its
required ``nodeSelectorTerms`` (NodeAffinity's filter), nor to a node that
carries a ``NoSchedule`` or ``NoExecute`` taint that no toleration of the
pod matches (TaintToleration's filter).

Imports nothing: what it is given is all it has.  Pod ``i`` has the shape
``pattern[i % len(pattern)]``: its ``node_selector``, its raw
``node_affinity`` and its raw ``tolerations``, after the toleration of
``kwok.x-k8s.io/node`` that every pod of make_pods carries unless the shape
says ``tolerate_kwok: false``.  Node ``i`` is what make_nodes makes of the
configuration's ``nodes`` object: ``type=kwok``, ``kwok-group`` = ``i`` mod
10 (make_nodes' ten groups), zone ``zone-<i mod zones>``, region
``region-<i mod regions>``, its own name as ``kubernetes.io/hostname``, and
the labels ``group_labels`` gives its group; the taints of ``node_taints``, and
those ``group_taints`` gives its group.  A cordon is no taint of the node
object: the base replay's ``bound_to_cordoned`` holds it.
"""

KWOK_GROUPS = 10
KWOK_TOLERATION = {"key": "kwok.x-k8s.io/node", "operator": "Exists"}
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def node_labels(nodes, i):
    group = str(i % KWOK_GROUPS)
    name = f"{nodes.get('prefix', 'kwok-node')}-{i}"
    return {
        "type": "kwok",
        "kwok-group": group,
        "topology.kubernetes.io/zone": f"zone-{i % int(nodes.get('zones', 8))}",
        "topology.kubernetes.io/region": f"region-{i % int(nodes.get('regions', 4))}",
        "kubernetes.io/hostname": name,
        **(nodes.get("group_labels") or {}).get(group, {}),
    }


def node_taints(nodes, i):
    group = str(i % KWOK_GROUPS)
    return [*(nodes.get("node_taints") or ()),
            *(nodes.get("group_taints") or {}).get(group, ())]


def _as_int(text):
    try:
        return int(text)
    except (TypeError, ValueError):
        return None


def expression_matches(expr, labels):
    """One ``matchExpressions`` entry against a node's labels."""
    op, key, values = expr["operator"], expr["key"], expr.get("values") or []
    if op == "In":
        return key in labels and labels[key] in values
    if op == "NotIn":
        return not (key in labels and labels[key] in values)
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    if op in ("Gt", "Lt"):
        have = _as_int(labels.get(key))
        want = _as_int(values[0]) if values else None
        if have is None or want is None:
            return False
        return have > want if op == "Gt" else have < want
    return False


def selector_matches(shape, labels):
    """``nodeSelector`` (every pair) and the required terms (any term, every
    expression of it; a term without expressions matches nothing)."""
    for key, value in (shape.get("node_selector") or {}).items():
        if labels.get(key) != value:
            return False
    required = (shape.get("node_affinity") or {}).get(REQUIRED)
    if required is None:
        return True
    return any(
        term.get("matchExpressions")
        and all(expression_matches(e, labels) for e in term["matchExpressions"])
        for term in required.get("nodeSelectorTerms") or ()
    )


def tolerations_of(shape):
    own = [KWOK_TOLERATION] if shape.get("tolerate_kwok", True) else []
    return own + list(shape.get("tolerations") or ())


def tolerates(toleration, taint):
    """v1.Toleration.ToleratesTaint."""
    if toleration.get("effect") and toleration["effect"] != taint.get("effect"):
        return False
    if toleration.get("key") and toleration["key"] != taint["key"]:
        return False
    if toleration.get("operator", "Equal") == "Exists":
        return True
    return bool(toleration.get("key")) and (
        toleration.get("value", "") == taint.get("value", ""))


def untolerated(shape, taints):
    """Whether some taint that forbids scheduling meets no toleration."""
    tolerations = tolerations_of(shape)
    return any(
        taint.get("effect") in ("NoSchedule", "NoExecute")
        and not any(tolerates(t, taint) for t in tolerations)
        for taint in taints
    )


def names_a_host(shape):
    """Whether the shape's selector or required terms name the one label
    that differs between two nodes of the same index mod ``cycle``."""
    keys = set(shape.get("node_selector") or {})
    required = (shape.get("node_affinity") or {}).get(REQUIRED) or {}
    for term in required.get("nodeSelectorTerms") or ():
        keys.update(e["key"] for e in term.get("matchExpressions") or ())
    return "kubernetes.io/hostname" in keys


def numbers(seen, replayed, *, nodes, pattern, offered):
    """But for its name, a node's labels and taints are those of its index
    mod ``cycle``: a (shape, node) pair is judged once for each such class,
    or once for each node where the shape names a host."""
    cycle = KWOK_GROUPS * int(nodes.get("zones", 8)) * int(nodes.get("regions", 4))
    by_host = [names_a_host(shape) for shape in pattern]
    verdict = {}
    mismatch = untol = 0
    period = len(pattern)
    for pod, node in zip(seen["bind_pod"].tolist(), seen["bind_node"].tolist()):
        if node < 0:
            continue                    # the base replay's unknown_node
        s = pod % period
        at = (s, node if by_host[s] else node % cycle)
        if at not in verdict:
            verdict[at] = (
                not selector_matches(pattern[s], node_labels(nodes, node)),
                untolerated(pattern[s], node_taints(nodes, node)),
            )
        wrong_labels, wrong_taints = verdict[at]
        mismatch += wrong_labels
        untol += wrong_taints
    return {"selector_mismatch": int(mismatch), "taint_untolerated": int(untol)}
