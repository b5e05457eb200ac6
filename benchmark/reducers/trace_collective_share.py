"""Share of the busiest chip's busy time that its collective operations
take: 100 * union of the collectives' intervals / union of all device
ops, on the operations of ``run["device_ops"]`` (the busiest of the
cell's chips, ``tracefile.cell_chips``; line ``XLA Ops``, never ``Async
XLA Ops``, which chip 0 alone fills). An op is a collective by its
opcode (the test of ``tests/chip_mesh_trace.py``, which read the first
four-chip trace): the trace names an op by its HLO line, ``%name =
shape opcode(...)``, and the opcode decides, not an operand that is a
collective's result; an event that is a bare name is taken by that
name. A trace in which no collective ran has nothing to read here: the
metric is left out, never reported as 0."""

import re

import stats

_KINDS = (r"(?:all-gather|all-reduce|all-to-all|reduce-scatter|"
          r"collective-permute|collective-broadcast|send|recv)"
          r"(?:-start|-done)?")
COLLECTIVE = re.compile(rf"(?<![%\w.-]){_KINDS}\(")
COLLECTIVE_NAME = re.compile(rf"^%?{_KINDS}(?:\.\d+)?$")


def is_collective(name: str) -> bool:
    _res, eq, line = name.partition(" = ")
    return bool(COLLECTIVE.search(line) if eq
                else COLLECTIVE_NAME.match(name.strip()))


def reduce(run: dict):
    ops = run.get("device_ops")
    if not ops:
        return None
    coll = [(s, d) for name, s, d in ops if is_collective(name)]
    if not coll:
        return None
    busy = stats.union_seconds([(s, d) for _n, s, d in ops])
    return 100.0 * stats.union_seconds(coll) / busy
