"""A child span's time inside a program span: the summed duration of the
`inside` spans that lie within each `span` span, mean over the `span` spans
whole in the traced window. The bridge's stages: dcn.bridge.stage_in (the
waits for the chunks' landings) and dcn.bridge.stage_out (the device_put
calls back) inside dcn.bridge, once a chunk each. None where the program has
no such span."""

from perfbench.readers import program_spans


def sums(t, lo: float, hi: float, span: str, inside: str) -> list:
    inner = program_spans.named(t, inside, lo, hi)
    if not inner:
        return []
    return [program_spans.inside(inner, s, d)
            for s, d in program_spans.named(t, span, lo, hi)]


def read(ctx: dict, params: dict):
    t = program_spans.load(ctx)
    if t is None:
        return None
    got = sums(t, ctx["lo"], ctx["hi"], params["span"], params["inside"])
    return sum(got) / len(got) if got else None
