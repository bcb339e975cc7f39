"""Round driver: relaxation sweeps per source solve (light sweeps plus
one heavy sweep per bucket, from the result's telemetry), over every
real lane answered. Reads ``driver.sweeps_per_solve.<cell kind>`` for
every kind of cell."""


def read(ctx):
    lanes = [b + i for r in ctx.requests for b, i in r.lanes]
    return sum(lanes) / len(lanes) if lanes else None
