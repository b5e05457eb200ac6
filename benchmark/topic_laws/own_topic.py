"""Topic law ``own_topic``: every owner has one literal topic of its
own, ``topic`` with ``{i}`` replaced by the owner's number, and the
pool is cut into ``owners`` sectors by the rule ``Plan.base`` starts a
publisher by (publisher ``i`` of ``owners`` begins at position
``i * pool // owners``): every position of sector ``i`` carries owner
``i``'s topic. With as many publishers as owners, publisher ``i``
publishes on its own topic and on no other until it has walked its
whole sector, as emqtt-bench's ``-t <prefix>/%i`` gives client ``i``.
Where ``owners`` divides the pool, position ``p`` belongs to owner
``p * owners // pool``. Nothing is drawn: the seed is not used."""

from __future__ import annotations


def pool(params: dict, vocab, seed: int) -> list:
    n, owners = params["pool"], params["owners"]
    topics = [params["topic"].format(i=i) for i in range(owners)]
    # the largest i with i * n // owners <= p
    return [topics[((p + 1) * owners - 1) // n] for p in range(n)]
