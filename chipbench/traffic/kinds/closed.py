"""Closed loop: ``clients`` keep-alive clients, each with one waited
submission outstanding. A client sends its next request as soon as the
previous one is answered, until the window and its tail have passed, so
the offered load follows the system's speed. Each request is due when it
is sent.
"""
import asyncio


def connections(traffic: dict, job: dict) -> int:
    return int(traffic["clients"])


async def drive(ctx, traffic: dict):
    end = ctx.seconds + ctx.tail_s

    async def client():
        while ctx.now() < end:
            await ctx.request(ctx.now())

    await asyncio.gather(*[client() for _ in range(int(traffic["clients"]))])
