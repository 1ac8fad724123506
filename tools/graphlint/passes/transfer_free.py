"""transfer-free: no host round-trip primitives inside any engine jit.

repro-lint's host-sync rule catches ``.item()`` / ``float()`` / ``np.*``
syncs lexically, but anything that survives into the *trace* — a
``jax.debug.print`` left behind, an ``io_callback`` smuggled in through a
helper, ``host_callback`` remnants — shows up in the jaxpr as a callback
or infeed/outfeed primitive and stalls the dispatch pipeline exactly the
same way.  This pass walks every equation (including sub-jaxprs) of every
registered jit and fails on any such primitive.
"""
from __future__ import annotations

from typing import List

from tools.graphlint.passes import iter_eqns
from tools.lint.report import Finding

PASS = "transfer-free"

# Primitive names that imply a host round-trip.  Substring match on
# "callback" covers pure_callback / io_callback / debug_callback and
# whatever jax renames them to next; ``jax.debug.print`` traces to its own
# ``debug_print`` primitive.
_BLOCKED_EXACT = {"infeed", "outfeed", "debug_print"}
_BLOCKED_SUBSTR = ("callback",)


def _blocked(prim_name: str) -> bool:
    if prim_name in _BLOCKED_EXACT:
        return True
    return any(s in prim_name for s in _BLOCKED_SUBSTR)


def check(entries, jaxprs) -> List[Finding]:
    """``jaxprs`` maps ``(entry.name, entry.key)`` to the entry's traced
    ClosedJaxpr (traced once by the CLI so passes never re-trace — a
    re-trace would corrupt the retrace pass's counters)."""
    findings: List[Finding] = []
    for entry in entries:
        closed = jaxprs.get((entry.name, entry.key))
        if closed is None:
            continue
        hit = set()
        for eqn in iter_eqns(closed.jaxpr):
            name = eqn.primitive.name
            if _blocked(name) and name not in hit:
                hit.add(name)
                findings.append(Finding(
                    file=entry.src_file, line=entry.src_line, col=0,
                    rule=PASS, severity="error",
                    message=(f"jit {entry.name}{entry.key}: primitive "
                             f"`{name}` performs a host round-trip inside "
                             "a compiled engine function")))
    return findings
