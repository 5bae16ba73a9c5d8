"""A ratio of native counters' deltas over the window: sum(num) over
sum(den) / den_scale."""


def read(ctx: dict, params: dict):
    c = ctx["run"].get("counters") or {}

    def total(names):
        hits = [v for k, v in c.items()
                if any(k == n or k == n + "_sum" for n in names)]
        return sum(hits) if hits else None

    num, den = total(params["num"]), total(params["den"])
    if num is None or not den:
        return None
    return num / (den / params.get("den_scale", 1))
