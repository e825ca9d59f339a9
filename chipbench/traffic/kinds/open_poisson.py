"""Open-loop Poisson arrivals at a fixed rate.

Parameters: ``rate`` (requests/s). A window of ``seconds`` holds exactly
``round(rate * seconds)`` requests. Their gaps are the quantiles of an
exponential distribution at the midpoints ``(k + 0.5) / n``, scaled to
fill the window, in an order drawn from the seed: every seed offers the
same gaps in another order, so the work does not change with the seed.
Requests are sent when due, whether or not earlier ones were answered,
and the tail after the window repeats the gaps at the same rate.
"""
import asyncio
import math
import random


def connections(traffic: dict, job: dict) -> int:
    """Keep-alive connections opened before the window: about 0.2 s of
    arrivals in flight, the rest opened on demand."""
    return min(4096, int(float(traffic["rate"]) * 0.2) + 32)


def gaps(rate: float, seconds: float, seed: int):
    n = max(1, round(rate * seconds))
    g = [-math.log1p(-(k + 0.5) / n) for k in range(n)]
    s = sum(g)
    g = [x * seconds / s for x in g]
    random.Random(seed ^ 0x5EED).shuffle(g)
    return g


async def drive(ctx, traffic: dict):
    g = gaps(float(traffic["rate"]), ctx.seconds, ctx.seed)
    end = ctx.seconds + ctx.tail_s
    t, k = 0.0, 0
    while t < end:
        delay = t - ctx.now()
        if delay > 0:
            await asyncio.sleep(delay)
        ctx.spawn(t)
        t += g[k % len(g)]
        k += 1
