"""Share of the two mixers' operations that the quadratic one asks for,
over the window's forwards: `mla_pair_flops / (mla_pair_flops +
kda_flops)` of the program's `model.delta.plan` records (one for each
padded length, made from shapes when that program was traced), the
median over the forwards the window ran. How the length mix splits the
mixers' work between the linear and the quadratic mechanism. A program
without the record yields `None`."""

import statistics

from benchmark import program_spans as ps

PLAN = "model.delta.plan"


def read(ctx):
    forwards = ctx["facts"].get("forwards")
    plans = {s.counts["tokens"]: s.counts for s in ps.recorded() or ()
             if s.name == PLAN and s.counts}
    if not forwards or not plans:
        return None
    shares = []
    for f in forwards:
        plan = plans.get(f["padded"])
        if f["id"] >= 0 and plan:
            both = plan["mla_pair_flops"] + plan["kda_flops"]
            if both:
                shares.append(100.0 * plan["mla_pair_flops"] / both)
    return statistics.median(shares) if shares else None
