"""Load generator for the served cells: a child process that never imports
JAX (stdlib ``asyncio`` only), so it neither holds the chip nor shares the
server's event loop and interpreter lock.

    python chipbench/loadgen.py <job.json>

``job.json`` names the server's address, the traffic file, the seed, the
window and the result file. The generator opens its keep-alive
connections, prints ``ready``, reads ``go <t0>`` from standard input
(``t0`` on the shared ``time.monotonic`` clock), runs the traffic kind's
``drive`` for the window plus a short tail, waits for every outstanding
answer up to the drain limit, writes one record per request to the result
file and prints ``done``.

A record holds the request's due time and send time (offsets from ``t0``),
its answer time (or -1 when none came), the HTTP status and the answer's
``id``, ``status``, ``label``, ``conf`` and ``votes``; ``loop_lag`` holds
the generator's longest late wake-up and when it came. Latency is taken from
the due time, so a stall of the generator or the server counts against the
requests it delays.
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import math
import pathlib
import random
import resource
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent

# the word lists of the text generator: a closed vocabulary of short-review
# words, so text length (not vocabulary) sets the encoder's work. A text of
# class k takes a share of its words from _CLASS_WORDS[k] and the rest from
# _WORDS, so its label can be learned from its words
_WORDS = (
    "the a this that movie film plot story actor acting scene scenes script "
    "is was are were very quite really not never always too so and but or "
    "long short simple music score camera shot ending opening cast director "
    "writer character watch see think find make take give "
    "of in on at by for with about from into over after before than as"
).split()
_CLASS_WORDS = (
    "bad awful dull boring slow cold dark thin flat hate miss weak".split(),
    "good great fine funny moving sharp smart clever warm bright rich "
    "love".split(),
)


def load_kind(kind: str):
    """The traffic kind's module, ``traffic/kinds/<kind>.py``."""
    path = HERE / "traffic" / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic kind {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def text_lengths(n: int, p: dict):
    """Word counts of ``n`` texts: the quantiles at ``(k + 0.5) / n`` of a
    log-normal with median ``median_words`` and log-sd ``sigma``, clipped
    to ``[min_words, max_words]`` (heavy right tail, like sentence
    lengths). Every seed gets this same multiset, in its own order."""
    med, sig = float(p["median_words"]), float(p["sigma"])
    lo, hi = int(p["min_words"]), int(p["max_words"])
    from statistics import NormalDist
    z = NormalDist()
    return [min(hi, max(lo, int(round(med * math.exp(sig * z.inv_cdf(q))))))
            for q in ((k + 0.5) / n for k in range(n))]


class Payloads:
    """The body of each request, drawn from the seed. With a ``text``
    block in the traffic file every submission carries a label drawn from
    the seed and a text of a drawn length, cycling through one fixed
    multiset of lengths in a seeded order; each word comes from the
    label's word list with probability ``class_words``, else from the
    shared list."""

    def __init__(self, traffic: dict, seed: int, n_classes: int,
                 timeout_s: float):
        self.rng = random.Random(seed)
        self.text = traffic.get("text")
        self.n_classes = n_classes
        self.timeout_s = timeout_s
        if self.text:
            if n_classes > len(_CLASS_WORDS):
                raise ValueError(f"the text generator has word lists for "
                                 f"{len(_CLASS_WORDS)} classes, not "
                                 f"{n_classes}")
            self.lengths = text_lengths(1024, self.text)
            self.rng.shuffle(self.lengths)
            self.share = float(self.text["class_words"])
            self._k = 0

    def __call__(self) -> dict:
        body = {"wait": True, "timeout_s": self.timeout_s}
        if self.text:
            n = self.lengths[self._k % len(self.lengths)]
            self._k += 1
            label = self.rng.randrange(self.n_classes)
            own, r = _CLASS_WORDS[label], self.rng
            body["text"] = " ".join(
                r.choice(own) if r.random() < self.share else r.choice(_WORDS)
                for _ in range(n))
            body["label"] = label
        return body


class Conn:
    """One keep-alive HTTP/1.1 connection to the label server."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.reader = self.writer = None

    async def open(self):
        self.reader, self.writer = await asyncio.open_connection(
            self.host, self.port)
        return self

    async def post(self, path: str, obj: dict):
        body = json.dumps(obj).encode()
        self.writer.write((
            f"POST {path} HTTP/1.1\r\nHost: {self.host}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await self.writer.drain()
        line = await self.reader.readline()
        if not line:
            raise ConnectionResetError("server closed the connection")
        status = int(line.split()[1])
        n = 0
        while True:
            h = await self.reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            if k.strip().lower() == "content-length":
                n = int(v)
        data = await self.reader.readexactly(n) if n else b""
        return status, (json.loads(data) if data else None)

    def close(self):
        if self.writer is not None:
            self.writer.close()


class Ctx:
    """What a traffic kind drives: a pool of keep-alive connections, the
    request bodies, the clock and the per-request records."""

    def __init__(self, job: dict, traffic: dict):
        self.host, self.port = job["host"], int(job["port"])
        self.seed = int(job["seed"])
        self.seconds = float(job["seconds"])
        self.tail_s = float(job["tail_s"])
        self.payload = Payloads(traffic, self.seed, int(job["n_classes"]),
                                float(job["drain_s"]))
        self.idle: list = []
        self.t0 = 0.0
        self.rec = dict(due=[], sent=[], answered=[], http=[], id=[],
                        status=[], label=[], conf=[], votes=[])
        self.tasks: set = set()
        self.loop_lag = [0.0, 0.0]      # (when, longest late wake-up) in s

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def preopen(self, n: int):
        conns = await asyncio.gather(*[Conn(self.host, self.port).open()
                                       for _ in range(n)])
        self.idle.extend(conns)

    async def request(self, due: float):
        """Send one waited submission now on an idle connection (a new one
        if none is idle) and record it against its due time."""
        body = self.payload()
        conn = self.idle.pop() if self.idle else None
        i = len(self.rec["due"])
        r = self.rec
        r["due"].append(due)
        r["sent"].append(self.now())
        for k in ("answered", "http", "id", "label", "conf", "votes"):
            r[k].append(-1)
        r["status"].append("none")
        try:
            if conn is None:
                conn = await Conn(self.host, self.port).open()
            status, ans = await conn.post("/tasks", body)
        except (OSError, asyncio.IncompleteReadError, ValueError,
                IndexError) as e:
            r["status"][i] = f"error:{type(e).__name__}"
            if conn is not None:
                conn.close()
            return
        self.idle.append(conn)
        r["answered"][i] = self.now()
        r["http"][i] = status
        ans = ans or {}
        r["id"][i] = ans.get("id", -1)
        r["status"][i] = ans.get("status", "none")
        r["label"][i] = ans.get("label", -1)
        r["conf"][i] = ans.get("conf", -1.0)
        r["votes"][i] = ans.get("votes", -1)

    def spawn(self, due: float):
        t = asyncio.get_running_loop().create_task(self.request(due))
        self.tasks.add(t)
        t.add_done_callback(self.tasks.discard)


async def watch_lag(ctx, every_s: float = 0.005):
    """Keep the longest time this process's event loop woke up late, and
    when: a stall of the generator itself (descheduled, or held in one
    callback) shows here and not on the server's side."""
    while True:
        t = time.monotonic()
        await asyncio.sleep(every_s)
        lag = time.monotonic() - t - every_s
        if lag > ctx.loop_lag[1]:
            ctx.loop_lag = [ctx.now(), lag]


async def _main(job: dict) -> int:
    traffic = json.loads(pathlib.Path(job["traffic_file"]).read_text())
    kind = load_kind(traffic["kind"])
    ctx = Ctx(job, traffic)
    await ctx.preopen(kind.connections(traffic, job))
    print("ready", flush=True)
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    ctx.t0 = float(line.split()[1])
    watcher = asyncio.get_running_loop().create_task(watch_lag(ctx))
    await kind.drive(ctx, traffic)
    # drain: every request sent gets until the limit to be answered
    if ctx.tasks:
        await asyncio.wait(list(ctx.tasks), timeout=max(
            0.0, float(job["drain_s"]) - (ctx.now() - ctx.seconds
                                          - ctx.tail_s)))
    watcher.cancel()
    for c in ctx.idle:
        c.close()
    pathlib.Path(job["result_file"]).write_text(json.dumps(
        dict(ctx.rec, loop_lag=ctx.loop_lag)))
    print("done", flush=True)
    return 0


def main(argv=None) -> int:
    # the generator's heap (its records) only grows during a run, and a
    # full collection over it stalls every request due meanwhile
    gc.disable()
    argv = sys.argv[1:] if argv is None else argv
    job = json.loads(pathlib.Path(argv[0]).read_text())
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    return asyncio.run(_main(job))


if __name__ == "__main__":
    sys.exit(main())
