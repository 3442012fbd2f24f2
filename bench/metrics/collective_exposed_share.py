"""Share of the traced window in which the cell's first chip runs a
collective operation and no other operation: the union of its
collectives' intervals (``collective-permute``, ``all-gather``,
``all-reduce``, ``reduce-scatter``, ``all-to-all``, with their ``-start``
and ``-done`` halves, the waits included, found by opcode or by the
instruction's name) less the part that other operations cover, over the
window. That is the exchange between chips that compute does not hide.
Layer: collectives."""

import re

import traces

COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "reduce-scatter", "all-to-all")
OPCODE = re.compile(r"([a-z][\w-]*)\(")


def opcode(hlo: str) -> str:
    """The opcode of an HLO instruction's text, ``%name = shape op(...)``:
    the first word followed by ``(`` once the shape, which may be a tuple
    holding parentheses of its own, is passed."""
    rest = hlo.split(" = ", 1)[-1]
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    m = OPCODE.search(rest)
    return m.group(1) if m else ""


def is_collective(hlo: str) -> bool:
    """By opcode, or by instruction name where the compiler wrapped the
    collective in a fusion named after it."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return any(w == c or w.startswith((c + "-", c + "."))
               for w in (opcode(hlo), name) for c in COLLECTIVES)


def collective_names(ops: list) -> set:
    """The instruction texts of ``ops`` that are collectives, each text
    parsed once however often it ran."""
    return {n for n in {e.name for e in ops} if is_collective(n)}


def exposed_ns(ops: list) -> int:
    """Nanoseconds in which a collective of ``ops`` runs and no other
    operation does: |C | N| - |N|."""
    names = collective_names(ops)
    if not names:
        return 0
    rest = [e for e in ops if e.name not in names]
    return traces.union_ns(ops) - traces.union_ns(rest)


def read(run):
    first = next(iter(run.ops.values()))
    if run.hi <= run.lo or not collective_names(first):
        return None
    return 100.0 * exposed_ns(first) / (run.hi - run.lo)
