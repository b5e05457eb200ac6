"""Share of the busiest chip's busy time that the operations of some
opcodes take: 100 * union of their intervals / union of all device
ops, on the operations of ``run["device_ops"]`` (the busiest of the
cell's chips, ``tracefile.cell_chips``; line ``XLA Ops``). An op is
told by its opcode, as ``trace_collective_share`` tells a collective:
the trace names an op by its HLO line, ``%name = shape opcode(...)``,
and the opcode decides, not an operand that is such an op's result; an
event that is a bare name is taken by that name. ``opcodes`` =
``["while"]`` reads the automaton walk, the one loop of the one-chip
served path (``ops/match.py::match_batch``); the ops of its body lie
inside its interval. A trace in which no such op ran has nothing to
read here: the metric is left out, never reported as 0."""

import re

import stats


def reduce(run: dict, opcodes: list):
    ops = run.get("device_ops")
    if not ops:
        return None
    kinds = "|".join(re.escape(k) for k in opcodes)
    by_line = re.compile(rf"(?<![%\w.-])(?:{kinds})\(")
    by_name = re.compile(rf"^%?(?:{kinds})(?:\.\d+)?$")

    def told(name: str) -> bool:
        _res, eq, line = name.partition(" = ")
        return bool(by_line.search(line) if eq
                    else by_name.match(name.strip()))

    mine = [(s, d) for name, s, d in ops if told(name)]
    if not mine:
        return None
    busy = stats.union_seconds([(s, d) for _n, s, d in ops])
    return 100.0 * stats.union_seconds(mine) / busy
