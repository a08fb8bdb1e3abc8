"""Device milliseconds per tick of the frontend's 12 -> 10-bit log ROM
read (the ops under ``kws_frontend/kws_log_rom``), on the busiest device,
from the program's scopes in the traced slice (`bench.spans.readings`)."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if spans is None:
        return None
    return spans["scope_ms"].get("kws_frontend/kws_log_rom")
