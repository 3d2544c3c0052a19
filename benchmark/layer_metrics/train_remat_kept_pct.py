"""Share of the layers' forward FLOPs that the backward pass does not
run again, from the program's `train.remat_plan` record (one when the
train step is traced; counts `layers`, `recompute_flops`,
`layer_forward_flops` among others): 100 x (1 - recompute_flops /
(layers x layer_forward_flops)). 100 where every layer's activations
are kept; a layer recomputed whole still reads 16 at Mistral's widths,
because its down projection is never run again. A program without the
record (an older commit) yields `None`."""

from benchmark import program_spans as ps

PLAN = "train.remat_plan"


def read(ctx):
    plans = [s.counts for s in ps.recorded() or ()
             if s.name == PLAN and s.counts]
    if not plans:
        return None
    plan = plans[-1]            # the window's step is the last one traced
    forward = plan["layers"] * plan["layer_forward_flops"]
    return 100.0 * (1.0 - plan["recompute_flops"] / forward) \
        if forward else None
