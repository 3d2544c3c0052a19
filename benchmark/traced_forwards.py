"""The forwards of a serve cell that lie wholly inside its traced
window, each with the device operations it ran and the replica's facts
about it. No jax here.

The deployment answers one request at a time and reads its result
inside its `forward` span, so every device operation of a forward lies
inside that span; the profile runs to the end of the run, so its
`forward` spans are the last forwards the replica recorded, in order.
"""

from __future__ import annotations

from typing import List, Optional


def whole_forwards(ctx: dict) -> Optional[List[dict]]:
    """[{"facts": the driver's record of the forward, "ops": [(name,
    start, seconds)]}] or None where the driver gives no such facts (an
    older driver, an older program)."""
    facts = ctx["facts"].get("forwards")
    t = ctx["trace"]
    if not facts:
        return None
    spans = sorted((s for s in t["spans"] if s[0] == "forward"),
                   key=lambda s: s[1])
    if not spans or len(spans) > len(facts):
        return None
    lo, hi = t["window"]
    ops = sorted(t["inside"][min(t["inside"])], key=lambda e: e[1])
    out = []
    for (_n, start, dur), fact in zip(spans, facts[-len(spans):]):
        if start < lo or start + dur > hi:
            continue
        out.append({"facts": fact, "ops": [
            e for e in ops if start <= e[1] < start + dur]})
    return out


def kernel_seconds(forward: dict, kernel: str) -> float:
    """Device seconds of the forward's operations named `kernel` (a
    signature under `kernels/`) or `kernel.<n>` (an HLO instruction the
    program named)."""
    return sum(d for name, _s, d in forward["ops"]
               if name == kernel or name.startswith(kernel + "."))
