"""Broker waves (flush_async and synchronous flush calls that carried
requests) per service wave; 1.0 means the double buffer holds."""


def read(ctx):
    w = ctx.counts["service_waves"]
    return ctx.counts["broker_waves"] / w if w else None
