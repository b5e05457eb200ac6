"""The controls: each breaks one guarantee that the configuration
states, underneath the timed path, so that ``correct`` is shown to
fail. Handed to ``run.main(..., sabotage=...)``, which calls it with
the ``Run`` just before the measured window. Used by the CPU rehearsal
(``test_rehearsal.py``) and, at the cell's own size, by
``chip_control.py``."""

from __future__ import annotations


def _tamper_once(run, change, client_id: str = "bench-sub-0") -> None:
    """Apply ``change`` to the first non-empty batch of frames that the
    broker hands one subscriber socket's connection."""
    chan = run.node.cm.lookup_channel(client_id)
    if chan is None:
        raise RuntimeError(f"no live channel {client_id!r}")
    session = chan.session
    drain = session.drain_outbox
    left = [1]

    def _drain():
        out = drain()
        if out and left[0]:
            left[0] = 0
            out = change(out)
        return out

    session.drain_outbox = _drain


def drop_delivery(run) -> None:
    """One message that the broker queued for one subscriber socket is
    never written: 'arrives exactly once' broken by a loss."""
    _tamper_once(run, lambda out: out[1:])


def duplicate_delivery(run) -> None:
    """One message is written twice to one subscriber socket."""
    _tamper_once(run, lambda out: out[:1] + out)


def host_fallback(run) -> None:
    """One device walk fails; the product's breaker serves that batch
    from the host and every delivery is still right — the device did
    not do the work."""
    from emqx_tpu import faults

    faults.set_master(True)
    faults.arm("device.walk", times=1)


def wrong_filter(run) -> None:
    """The walk's answers are wrong: the ids of the filters that the
    sampled topics match come out as other filters of the population
    (their entries in the router's id -> filter snapshot are swapped
    with filters that match no sampled topic). Every socket still gets
    its messages; only the comparison of the in-process subscriber's
    filters with the plain trie can tell."""
    router = run.node.router
    router.automaton()  # the snapshot the window will serve from
    held = {f for fl in run.plan.sockets for f in fl}  # by a socket
    hit = {f for fl in run.ref_matches for f in fl} - held
    if not hit:
        raise RuntimeError("no sampled topic matches a filter")
    others = (f for f in router._filter_ids
              if f not in hit and f not in held)
    id_map = router._auto_map
    for f in sorted(hit):
        a, z = router._filter_ids[f], router._filter_ids[next(others)]
        id_map[a], id_map[z] = id_map[z], id_map[a]


def undo_host_fallback() -> None:
    from emqx_tpu import faults

    faults.clear()
    faults.set_master(False)


ALL = {"drop_delivery": drop_delivery,
       "duplicate_delivery": duplicate_delivery,
       "host_fallback": host_fallback,
       "wrong_filter": wrong_filter}
