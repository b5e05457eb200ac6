"""The plain reference: MQTT topic matching written from the OASIS
specification (MQTT 3.1.1 §4.7), with no import from the program.

Two forms of the same semantics, so each checks the other's users:

- :func:`matches` — the per-filter predicate. The load generator uses
  it to say which socket must receive which topic.
- :func:`share_of` — a shared subscription's group and filter, and
  the rule a group is held to.
- :class:`Trie` — a dict trie over a whole filter population. The
  benchmark uses it to say which of the deployment's filters must
  deliver a sampled topic to the in-process subscriber.

Run as a program it is the trie child: one JSON request on stdin
(``population``, ``publish_topics``, ``seed``, ``positions``), one
JSON answer on stdout (for each pool position the sorted list of
matching filters). It builds the population and the topic pool from
the seed itself and takes nothing the program made.
"""

from __future__ import annotations

import json
import os
import sys
import time


def matches(topic: str, flt: str) -> bool:
    """True when topic name ``topic`` matches topic filter ``flt``.

    §4.7.1.2 ``#`` matches the parent and any number of child levels;
    §4.7.1.3 ``+`` matches exactly one level; §4.7.2 a filter that
    starts with a wildcard does not match a topic that starts with
    ``$``."""
    t = topic.split("/")
    f = flt.split("/")
    if t[0].startswith("$") and f[0] in ("+", "#"):
        return False
    for i, w in enumerate(f):
        if w == "#":
            return True
        if i >= len(t):
            return False
        if w != "+" and w != t[i]:
            return False
    return len(t) == len(f)


def share_of(flt: str):
    """``$share/<group>/<filter>`` -> ``(group, filter)``; a plain
    filter -> ``(None, flt)``.

    The rule a shared subscription is held to (MQTT 5 §4.8.2, which
    EMQX serves to 3.1.1 clients too): the sessions that subscribed
    ``$share/<group>/<filter>`` are one subscriber between them. A
    message whose topic matches ``filter`` (by :func:`matches`) goes
    to exactly one session of the group: never to two, never to none,
    whichever the broker's strategy picks. Which one is not stated, so
    the comparison is the group's: the deliveries of all its sockets
    together hold each matching message once for each of the group's
    filters that matches."""
    if flt.startswith("$share/"):
        parts = flt.split("/", 2)
        if len(parts) != 3 or not parts[1] or not parts[2]:
            raise ValueError(f"no shared subscription: {flt!r}")
        return parts[1], parts[2]
    return None, flt


class Trie:
    """A dict trie: one node per filter level, ``"+"`` and ``"#"``
    are ordinary keys that the walk treats by the rules above."""

    __slots__ = ("root",)
    _END = 0  # key under which a node keeps the filter that ends there

    def __init__(self) -> None:
        self.root: dict = {}

    def insert(self, flt: str) -> None:
        node = self.root
        for w in flt.split("/"):
            nxt = node.get(w)
            if nxt is None:
                nxt = node[w] = {}
            node = nxt
        node[self._END] = flt

    def match(self, topic: str) -> list:
        """Every inserted filter that matches ``topic``, sorted."""
        t = topic.split("/")
        out: list = []
        sys_topic = t[0].startswith("$")
        stack = [(self.root, 0)]
        end = self._END
        while stack:
            node, i = stack.pop()
            h = node.get("#")
            if h is not None and not (i == 0 and sys_topic):
                out.append(h[end])
            if i == len(t):
                f = node.get(end)
                if f is not None:
                    out.append(f)
                continue
            nxt = node.get(t[i])
            if nxt is not None:
                stack.append((nxt, i + 1))
            nxt = node.get("+")
            if nxt is not None and not (i == 0 and sys_topic):
                stack.append((nxt, i + 1))
        out.sort()
        return out


def _child() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    if here not in sys.path:
        sys.path.insert(0, here)
    import importlib

    req = json.loads(sys.stdin.readline())
    t0 = time.monotonic()
    pop = importlib.import_module("populations." + req["population"]["kind"])
    filters, vocab = pop.build(req["population"], req["seed"])
    trie = Trie()
    for f in filters:
        trie.insert(f)
    law = req["publish_topics"]
    pool = importlib.import_module("topic_laws." + law["law"]).pool(
        law, vocab, req["seed"])
    answer = {"filters": len(filters),
              "matches": [trie.match(pool[i]) for i in req["positions"]],
              "build_s": time.monotonic() - t0}
    sys.stdout.write(json.dumps(answer) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(_child())
