"""Self time of a program span: its duration less that of the named spans
inside it, summed over the traced window and divided by the number of such
spans. fit()'s own host time a step is train.step less train.step_fn less
train.loss_fetch."""

from perfbench.readers import program_spans


def self_seconds(t, lo: float, hi: float, span: str, less) -> list:
    children = [program_spans.named(t, name, lo, hi) for name in less]
    return [d - sum(program_spans.inside(c, s, d) for c in children)
            for s, d in program_spans.named(t, span, lo, hi)]


def read(ctx: dict, params: dict):
    t = program_spans.load(ctx)
    if t is None:
        return None
    own = self_seconds(t, ctx["lo"], ctx["hi"], params["span"], params["less"])
    if not own:
        return None
    return sum(own) / len(own)
