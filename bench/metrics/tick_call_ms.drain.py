"""Host milliseconds per tick inside the program's ``kws.server.tick_call``
span, the jitted tick call with its slab and mask transfer, over the
traced slice (`bench.spans.readings`)."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    return None if spans is None else spans["tick_call_ms"]
