"""{"kind": "compile_meter", "phase": "setup" | "window",
"fields": ["compile_s"]}: the sum of the named CompileMeter totals over
set-up or over the window (``compiles`` = programs built, compiled or
loaded from the persistent cache: one inside the window is a shape
that set-up did not warm; ``cache_misses`` = really compiled)."""


def read(source, ctx):
    totals = ctx.get("compile", {}).get(source["phase"])
    if totals is None:
        return None
    return sum(totals[f] for f in source["fields"])
