"""Device milliseconds per tick of the raw-audio frontend: every op under
the tick's ``kws_frontend`` scope (filter scan, quantizer, log ROM,
normalizer), on the busiest device, from the program's scopes in the
traced slice (`bench.frontend_ops.frontend_ms`)."""

from bench import frontend_ops


def read(ctx):
    return frontend_ops.frontend_ms(ctx)
