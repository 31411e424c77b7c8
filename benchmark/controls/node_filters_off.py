"""affinity-100k's control (door 5): the device sees neither a selector
nor a taint it may not pass.  Every pod is encoded without its
``nodeSelector`` and without its required ``nodeSelectorTerms``, and as a
pod that tolerates every taint a node object carries (the cordon's, which
the table keeps as a taint of its own, stays untolerated), whichever
intake lane brought it; its preferred terms, its requests and its record
in the store stay what they were.  The filters
that are left (the cordons, capacity) still hold, so every pod is bound
once to an open node with room: a ``group-3`` or ``dedicated`` pod lands
on its own tenth of the nodes one time in ten, a ``zone-pair`` pod in its
two zones one time in four, and a tenth of ``plain`` and ``zone-pair``
binds land on the tainted pool.  ``selector_mismatch`` and
``taint_untolerated`` (references/affinity.py) read far above 0 and
nothing else moves."""


def plant(store, coord):
    import dataclasses

    from k8s1m_tpu.snapshot.node_table import UNSCHEDULABLE_TAINT_KEY
    from k8s1m_tpu.snapshot.pod_encoding import PodBatchHost, Toleration

    fill_pod = PodBatchHost._fill_pod

    def blind(self, out, i, pod, qidx, taints):
        fill_pod(self, out, i, dataclasses.replace(
            pod, node_selector={}, required_terms=[], tolerations=[
                Toleration(key=key) for _id, (key, _value, _effect) in taints
                if key != UNSCHEDULABLE_TAINT_KEY],
        ), qidx, taints)

    PodBatchHost._fill_pod = blind

    def undo():
        PodBatchHost._fill_pod = fill_pod

    return undo
