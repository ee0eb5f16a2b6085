"""{"kind": "counter", "name": n} reads one counter;
{"kind": "counter", "num": [..], "den": [..]} the ratio of two sums of
counters (None while the denominator is 0). Counters are exact counts
taken by the program, as deltas over the window."""


def read(source, ctx):
    counters = ctx.get("counters", {})
    if "name" in source:
        return counters.get(source["name"])
    names = list(source["num"]) + list(source["den"])
    if any(n not in counters for n in names):
        return None
    den = sum(counters[n] for n in source["den"])
    if not den:
        return None
    return sum(counters[n] for n in source["num"]) / den
