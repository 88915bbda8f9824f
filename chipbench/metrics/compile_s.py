"""Seconds JAX spent making programs during set-up: tracing, lowering,
backend compilation and persistent-cache reads (``clock.EVENTS``)."""


def read(rec):
    return sum(seconds for _, seconds in rec.compile.values())
