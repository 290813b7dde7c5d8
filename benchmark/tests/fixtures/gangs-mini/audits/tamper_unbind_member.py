"""A negative of the fixture, not an audit: unbinds one member of a gang
that is bound whole, behind the store's back, so that `gang_atomicity`,
listed after it, has something to find."""

from audits.gang_atomicity import POD_GROUP_LABEL


def audit(cluster) -> list:
    for pod in cluster.pods.values():
        if pod.node_name is not None and pod.labels.get(POD_GROUP_LABEL):
            pod.node_name = None
            break
    return []
