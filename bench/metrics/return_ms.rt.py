"""Milliseconds per tick outside the device's own work between the start
of the tick call and the end of the scores' fetch: the launch lag (device
work's start - ``kws.server.tick_call``'s start) plus the scores lag
(``kws.handle.fetch``'s end - device work's end), means over the traced
slice (`bench.spans.tick_lags`). Each lag carries the profiler's offset
between the host's and the device's clock, with opposite signs; their
sum does not."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if spans is None:
        return None
    launch, scores = spans["launch_lag_ms"], spans["scores_lag_ms"]
    if launch is None or scores is None:
        return None
    return launch + scores
