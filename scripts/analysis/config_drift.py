"""RD22x — closed-schema config sections vs ``etc/emqx_tpu.toml``.

Every ``[section]`` that ``emqx_tpu/config.py`` parses with a closed
schema (unknown keys are startup errors) is backed by a dataclass;
the example config is the operator's only discovery surface for
those knobs. Two rules keep them in lockstep:

  RD221  a schema field has no line in the example toml — neither a
         live ``key = ...`` nor a commented ``# key = ...`` default.
         A knob that exists but is undiscoverable is how operators
         end up patching source.
  RD222  the example toml carries a key the schema does not know —
         the node would refuse to boot from its own example (or the
         key was renamed and the example silently rotted).

The schema is read from the AST (dataclass field names), never by
importing broker modules — the gate must run in milliseconds with no
jax in sight. Zones/listeners/modules sections are open-keyed
per-instance tables and are out of scope here.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from analysis import Finding

RULES = {
    "RD221": "config schema key missing from etc/emqx_tpu.toml",
    "RD222": "etc/emqx_tpu.toml key unknown to the config schema",
}

#: section -> (module file, dataclass name). ``node`` is special: its
#: keys live in a literal tuple inside config.parse_config.
SECTIONS: Dict[str, Tuple[str, str]] = {
    "matcher": ("emqx_tpu/router.py", "MatcherConfig"),
    "telemetry": ("emqx_tpu/telemetry.py", "TelemetryConfig"),
    "tracing": ("emqx_tpu/tracing.py", "TracingConfig"),
    "dispatch": ("emqx_tpu/broker.py", "DispatchConfig"),
    "overload": ("emqx_tpu/overload.py", "OverloadConfig"),
    "faults": ("emqx_tpu/faults.py", "FaultsConfig"),
    "durability": ("emqx_tpu/durability.py", "DurabilityConfig"),
    "cluster": ("emqx_tpu/cluster.py", "ClusterConfig"),
    "drain": ("emqx_tpu/drain.py", "DrainConfig"),
}

_SECTION_RE = re.compile(r"^#?\s*\[\[?([a-z_.]+)\]\]?\s*$")
_KEY_RE = re.compile(r"^#?\s?([a-z_][a-z0-9_]*)\s*=\s*\S")


def load_schema(ctx) -> None:
    """Populate ``ctx.schema`` from the dataclass ASTs."""
    root = ctx.root
    for section, (rel, clsname) in SECTIONS.items():
        p = root / rel
        if not p.exists():
            continue
        try:
            tree = ast.parse(p.read_text(encoding="utf-8"))
        except SyntaxError:
            continue
        for node in tree.body:
            if isinstance(node, ast.ClassDef) and \
                    node.name == clsname:
                fields = {}
                for sub in node.body:
                    if isinstance(sub, ast.AnnAssign) and \
                            isinstance(sub.target, ast.Name) and \
                            not sub.target.id.startswith("_"):
                        fields[sub.target.id] = (rel, sub.lineno)
                ctx.schema[section] = fields
    # the [node] section: the literal key tuple in parse_config
    p = root / "emqx_tpu" / "config.py"
    if p.exists():
        try:
            tree = ast.parse(p.read_text(encoding="utf-8"))
        except SyntaxError:
            return
        for node in ast.walk(tree):
            if isinstance(node, ast.Compare) and node.comparators \
                    and isinstance(node.comparators[0], ast.Tuple):
                names = [e.value for e in node.comparators[0].elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str)]
                if "sys_interval" in names and "loops" in names:
                    ctx.schema["node"] = {
                        n: ("emqx_tpu/config.py", node.lineno)
                        for n in names}
                    break


def load_toml(ctx) -> None:
    """Populate ``ctx.toml_keys``: section -> {key -> line}, reading
    live AND commented-default lines (``# key = value``)."""
    p = ctx.root / ctx.toml_path
    if not p.exists():
        return
    section = ""
    for i, line in enumerate(
            p.read_text(encoding="utf-8").splitlines(), start=1):
        m = _SECTION_RE.match(line.strip())
        if m:
            section = m.group(1)
            ctx.toml_keys.setdefault(section, {})
            continue
        m = _KEY_RE.match(line.strip())
        # "true"/"false" open prose comments ("# false = legacy ...")
        # — never real keys, a boolean can't be a key name
        if m and section and m.group(1) not in ("true", "false"):
            ctx.toml_keys.setdefault(section, {}).setdefault(
                m.group(1), i)


def check(fi, ctx) -> List[Finding]:
    return []


def finalize(ctx) -> List[Finding]:
    out: List[Finding] = []
    if not ctx.schema or not ctx.toml_keys:
        return out
    for section, fields in sorted(ctx.schema.items()):
        toml = ctx.toml_keys.get(section)
        if toml is None:
            # whole section absent from the example — report once
            # per field so the fix (document the section) is sized
            toml = {}
        for field, (rel, line) in sorted(fields.items()):
            if field not in toml:
                out.append(Finding(
                    rel, line, "RD221",
                    f"[{section}] {field} is not shown in "
                    f"{ctx.toml_path} — add a live or commented "
                    f"`# {field} = <default>` line so the knob is "
                    f"discoverable"))
        for key, line in sorted(toml.items()):
            if key not in fields:
                out.append(Finding(
                    ctx.toml_path, line, "RD222",
                    f"[{section}] {key} is not a known schema key — "
                    f"the example would fail validation (or the key "
                    f"was renamed and the example rotted)"))
    return out
