"""Share of the pairs an `eva` layer's queries see that are
query-summary pairs, over the window's forwards: `far_pairs /
(local_pairs + far_pairs)` of the program's `model.eva.plan` records
(one for each padded length, made from shapes when that program was
traced), each weighted by the forwards the window ran at that length.
How much of the mechanism the traffic reached: 0 where every prompt
fits one window. A program without the record yields `None`."""

from benchmark import program_spans as ps

PLAN = "model.eva.plan"


def read(ctx):
    forwards = ctx["facts"].get("forwards")
    plans = {s.counts["tokens"]: s.counts for s in ps.recorded() or ()
             if s.name == PLAN and s.counts}
    if not forwards or not plans:
        return None
    far = seen = 0
    for f in forwards:
        plan = plans.get(f["padded"])
        if f["id"] >= 0 and plan:
            far += plan["far_pairs"]
            seen += plan["far_pairs"] + plan["local_pairs"]
    return 100.0 * far / seen if seen else None
