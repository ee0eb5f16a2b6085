"""{"kind": "memory_stats", "field": "peak_bytes_in_use"}: the largest
value of the field over the devices, after the window."""


def read(source, ctx):
    values = [s[source["field"]] for s in ctx.get("memory_stats", ())
              if s and source["field"] in s]
    return max(values) if values else None
