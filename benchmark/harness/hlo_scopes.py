"""Which ``zoo_*`` scope an XLA-built device op belongs to. A v5e op event
carries its HLO instruction's name and, unless it is a custom call, no
scope; the compiled program's text carries, per instruction, the
``op_name`` its source had, ``jax.named_scope`` names included. Joining
the two by instruction name gives device time by scope."""

import re

LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?op_name="([^"]*)"')
TAG = re.compile(r"zoo_[a-z0-9_]+")


def scopes_by_instruction(hlo_text: str) -> dict:
    """{instruction name: [zoo_* tags in its op_name]} for every
    instruction of ``compiled.as_text()`` that has any."""
    out = {}
    for line in hlo_text.splitlines():
        m = LINE.match(line)
        if m:
            tags = TAG.findall(m.group(2))
            if tags:
                out[m.group(1)] = sorted(set(tags))
    return out


def seconds_under(reduced: dict, op_scopes: dict, pattern: str) -> float:
    """Device seconds (self time, mean over the devices) of the window's
    ops that a scope matching ``pattern`` covers: by the join for what XLA
    built, by the event's own text for a kernel."""
    rx = re.compile(pattern)
    total = 0.0
    for name, text, seconds, _ in reduced["ops"]:
        if any(rx.fullmatch(t) for t in op_scopes.get(name, ())) or \
                any(rx.fullmatch(t) for t in TAG.findall(text)):
            total += seconds
    return total / reduced["devices"]
