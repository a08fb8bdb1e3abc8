"""Device milliseconds per tick of the integer GRU's gate ROM reads (the
ops under a ``kws_gru{l}_gates`` scope), on the busiest device, from the
program's scopes in the traced slice (`bench.spans.readings`)."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    return None if spans is None else spans["gate_rom_ms"]
