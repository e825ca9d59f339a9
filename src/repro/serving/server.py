"""Live asyncio HTTP front end for the streaming label router.

The simulator measures simulated time; this module serves *real*
requests against wall-clock SLOs, the posture CLAMShell took on live
MTurk. A stdlib-only HTTP/1.1 service (``asyncio.start_server``) accepts
task submissions and label queries, micro-batches pending submissions
into the jitted serve tick each iteration — continuous batching, the
same shape as :mod:`repro.serving.scheduler`'s decode loop — and answers
queries from the finalized-label stream with per-request wall-clock
timestamps.

The router state is a donated device pytree (`serve_tick` aliases input
to output buffers), so window/backlog/pool arrays never round-trip to
host between ticks; the only per-tick host transfer is the small
``srv_*`` finalization bundle, packed into one buffer (``stats()``
counts the buffers fetched in ``tick_out_buffers_sum``: one per tick).
LM scenarios embed a tick's texts first; ``stats()`` counts them in
``embed_rows_sum`` and the rows the encoder ran in ``embed_rows_run_sum``.
Injection is throttled to each shard's free backlog capacity, so the
device never drops a request on its own — conservation ``submitted ==
answered + pending + in_system + dropped (+ shutdown)`` holds at every
tick boundary (tests/test_serving.py pins it under concurrent clients).

The loop's work is timed by ``repro.obs.timing`` spans, so a profiler
trace shows it beside the device's: ``serve.inject`` and ``serve.absorb``
(the host side of a tick), ``serve.tick`` (the device call and the fetch
of its answers; its child ``serve.dispatch`` in ``serve_tick`` returns on
dispatch, so the rest is the fetch), ``serve.refresh_tick`` (around
``serve.tick`` on the ticks whose step runs the offline Dawid-Skene
refresh, every ``refresh_every``-th; ``stats()`` counts them in
``refresh_ticks_sum``), ``serve.embed`` (LM scenarios),
``serve.yield`` (the event loop's other work, mostly HTTP, before the
next tick) and ``serve.idle`` (nothing pending).

Endpoints (JSON in/out):

  ``POST /tasks``          submit one task; body ``{"wait": bool,
                           "timeout_s": float}`` optional. ``wait`` long-
                           polls until the label finalizes or the timeout
                           fires (the TASK stays in the system; only the
                           HTTP wait times out). LM scenarios
                           (``features.kind="lm"``) also accept ``"text"``
                           (the task content — batch-embedded through the
                           LM encoder and injected into the tick in place
                           of a bank draw) and ``"label"`` (known true
                           class for accuracy accounting).
  ``GET /labels/<id>``     current state of a submission.
  ``GET /stats``           counters, conservation check, wall-clock
                           latency percentiles (of the most recent
                           ``LATENCY_WINDOW`` answers), running sums of
                           each answer's queue wait and ticks to answer,
                           ``repro.obs.timing`` rows.
  ``GET /healthz``         liveness.
  ``POST /shutdown``       graceful shutdown: stop accepting, drain.
"""
from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import json
import time
from typing import Optional

import numpy as np

from repro.obs import timing

# answers whose latency /stats percentiles cover (the most recent ones)
LATENCY_WINDOW = 1 << 16

_REASON = {200: "OK", 202: "Accepted", 400: "Bad Request", 404: "Not Found",
           429: "Too Many Requests", 503: "Service Unavailable"}


@dataclasses.dataclass
class _Req:
    """One submission's lifecycle. ``status`` walks pending (host queue)
    -> queued (on device) -> done | dropped | shutdown. ``t_inject`` and
    ``tick_inject`` mark its injection (the server's clock and tick
    count), ``tick_answer`` the tick that answered it."""
    rid: int
    event: asyncio.Event
    t_submit: float
    status: str = "pending"
    shard: int = -1
    uid: int = -1
    text: Optional[str] = None    # LM scenarios: embed-then-inject
    given_label: int = -1         # LM scenarios: known true label, or -1
    label: Optional[int] = None
    conf: float = 0.0
    votes: int = 0
    tis_s: float = 0.0
    t_answer: Optional[float] = None
    t_inject: Optional[float] = None
    tick_inject: int = -1
    tick_answer: int = -1

    def to_json(self) -> dict:
        d = dict(id=self.rid, status=self.status)
        if self.status == "done":
            d.update(label=self.label, conf=round(self.conf, 6),
                     votes=self.votes, tis_s=round(self.tis_s, 3),
                     latency_s=round(self.t_answer - self.t_submit, 6))
        return d


class LabelServer:
    """The live labeling service for one stream scenario.

    ``spec`` is a ``repro.scenarios.ScenarioSpec`` (its ``serve`` sub-spec
    carries host/port/timeouts; the workload+policy lower through
    ``to_serve_config``) or a ready serve-mode ``StreamConfig`` (then the
    keyword overrides supply the HTTP surface). Drive it either inside an
    existing event loop (``await server.start()`` ... ``await
    server.close()``) or via ``run_until_complete`` helpers in
    ``repro.launch.serve``.
    """

    def __init__(self, spec, *, seed: int = 0, host: str = None,
                 port: int = None, tick_interval_s: float = None,
                 max_pending: int = None, request_timeout_s: float = None,
                 drain_timeout_s: float = None):
        from repro.labelstream.router import (
            StreamConfig, _as_serve_config, _validate_serve_config,
        )

        self.cfg = _as_serve_config(spec)
        _validate_serve_config(self.cfg)
        sv = None if isinstance(spec, StreamConfig) else spec.serve
        pick = lambda ov, dflt: ov if ov is not None else dflt
        self.host = pick(host, sv.host if sv else "127.0.0.1")
        self.port = pick(port, sv.port if sv else 0)
        self.tick_interval_s = pick(tick_interval_s,
                                    sv.tick_interval_s if sv else 0.01)
        self.max_pending = pick(max_pending, sv.max_pending if sv else 4096)
        self.request_timeout_s = pick(request_timeout_s,
                                      sv.request_timeout_s if sv else 30.0)
        self.drain_timeout_s = pick(drain_timeout_s,
                                    sv.drain_timeout_s if sv else 10.0)
        self.seed = seed

        S = self.cfg.n_shards
        # LM scenarios accept real text: submissions carrying "text" are
        # batch-embedded on the tick thread and injected alongside the
        # simulated arrivals (NaN rows in the feat plan = "draw from the
        # bank as usual").
        self._lm = self.cfg.learner.feature_kind == "lm"
        self.state = None
        self._pending: collections.deque = collections.deque()
        self._reqs: dict = {}
        self._by_uid: dict = {}
        self._next_rid = 0
        # per-shard monotonic uid counters (every injected uid consumes a
        # slot whether or not it survives; int32 on device — documented
        # rollover at 2**31 tasks per shard)
        self._next_uid = np.zeros((S,), np.int64)
        self._backlog = np.zeros((S,), np.int64)   # host view, post-tick
        self.submitted = 0
        self.answered = 0
        self.dropped = 0
        self.rejected = 0
        self.shutdown_unanswered = 0
        self.ticks = 0
        self.t_sim = 0.0
        self._in_flight = 0
        self._lat = collections.deque(maxlen=LATENCY_WINDOW)
        # running sums over every answer: queue wait (submit to inject)
        # and ticks from injection to answer, counting both ends
        self.queue_wait_s_sum = 0.0
        self.answer_ticks_sum = 0
        # device buffers the tick fetches bring back, summed over ticks
        self.tick_out_buffers_sum = 0
        # ticks whose step ran the offline Dawid-Skene refresh
        self.refresh_ticks_sum = 0
        # texts embedded, and the rows the encoder's programs ran for them
        self.embed_rows_sum = 0
        self.embed_rows_run_sum = 0
        self._work: Optional[asyncio.Event] = None
        self._drained: Optional[asyncio.Event] = None
        self._closing = False
        self._closed = False
        self._server = None
        self._tick_task = None
        self._close_task = None
        self._stopped: Optional[asyncio.Event] = None
        # the exception that killed the tick loop, if one did
        self.error: Optional[BaseException] = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    async def start(self):
        from repro.labelstream.router import serve_init

        loop = asyncio.get_running_loop()
        self._work = asyncio.Event()
        self._drained = asyncio.Event()
        self._stopped = asyncio.Event()
        self.state = await loop.run_in_executor(
            None, serve_init, self.cfg, self.seed)
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._tick_task = asyncio.create_task(self._tick_loop())
        self._tick_task.add_done_callback(self._on_tick_loop_done)
        return self

    def _on_tick_loop_done(self, task):
        """A tick loop that raised (a failed compile, a device error) stops
        the server: waiters resolve as ``"shutdown"`` and
        :meth:`wait_closed` re-raises, instead of HTTP answering forever
        over a dead loop."""
        if task.cancelled() or task.exception() is None:
            return
        self.error = task.exception()
        self._close_task = asyncio.get_running_loop().create_task(
            self.close(drain=False))

    async def wait_closed(self):
        """Block until the server has closed (``POST /shutdown`` or
        :meth:`close`); raise the tick loop's exception if it died."""
        await self._stopped.wait()
        if self.error is not None:
            raise RuntimeError("serve tick loop failed") from self.error

    async def close(self, *, drain: bool = True):
        """Graceful shutdown: stop accepting (new submissions get 503),
        drain in-flight tasks up to ``drain_timeout_s``, then resolve any
        stragglers as ``"shutdown"`` and stop the tick loop."""
        if self._closed:
            return
        self._closing = True
        self._work.set()
        if drain and self.drain_timeout_s > 0 \
                and (self._pending or self._by_uid):
            try:
                await asyncio.wait_for(self._drained.wait(),
                                       self.drain_timeout_s)
            except asyncio.TimeoutError:
                pass
        self._closed = True
        if self._tick_task is not None:
            self._tick_task.cancel()
            # a failed loop's exception is kept in self.error
            await asyncio.gather(self._tick_task, return_exceptions=True)
        for req in list(self._pending) + list(self._by_uid.values()):
            if req.status in ("pending", "queued"):
                req.status = "shutdown"
                self.shutdown_unanswered += 1
                req.event.set()
        self._pending.clear()
        self._by_uid.clear()
        self._server.close()
        await self._server.wait_closed()
        self._stopped.set()

    # ------------------------------------------------------------------
    # tick driver (continuous batching)
    # ------------------------------------------------------------------
    def _inject_plan(self):
        """Micro-batch pending submissions into per-shard injection counts,
        least-loaded shard first, throttled to ``min(free backlog slots,
        max_arrivals_per_tick)`` per shard so the device cannot drop."""
        cfg = self.cfg
        S, M, Q = cfg.n_shards, cfg.max_arrivals_per_tick, cfg.backlog
        n_arr = np.zeros((S,), np.int32)
        room = np.minimum(M, Q - self._backlog)
        inject = []                   # (shard, slot, req) needing embed
        now = time.monotonic()
        while self._pending:
            s = int(np.argmax(room - n_arr))
            if room[s] - n_arr[s] <= 0:
                break
            req = self._pending.popleft()
            req.shard = s
            req.uid = int(self._next_uid[s]) + int(n_arr[s])
            req.status = "queued"
            req.t_inject, req.tick_inject = now, self.ticks
            self._by_uid[(s, req.uid)] = req
            if self._lm and (req.text is not None or req.given_label >= 0):
                inject.append((s, int(n_arr[s]), req))
            n_arr[s] += 1
        uid_base = self._next_uid.astype(np.int32)
        self._next_uid += n_arr
        return n_arr, uid_base, inject

    def _device_tick(self, n_arr, uid_base, inject=()):
        """Blocking jitted tick + transfer of the small srv_* bundle, one
        packed buffer (runs on the executor thread; the ``serve.tick``
        span lands in the ``repro.obs.timing`` registry, so the first
        call's compile shows up as the cold-vs-warm split). LM scenarios
        batch-embed any text-carrying submissions here (one encoder call
        per tick) and inject the vectors + known labels into this tick's
        arrivals."""
        import jax
        from repro.labelstream.router import serve_tick

        feat = labels = None
        if self._lm and inject:
            feat, labels = self._embed_plan(n_arr, inject)

        every = self.cfg.refresh_every
        refresh = every > 0 and self.ticks % every == every - 1
        with timing.span("serve.refresh_tick") if refresh \
                else contextlib.nullcontext(), timing.span("serve.tick"):
            self.state, out = serve_tick(self.cfg, self.state, n_arr,
                                         uid_base, feat=feat,
                                         labels=labels)
            host = jax.device_get(out)
        self.tick_out_buffers_sum += len(jax.tree_util.tree_leaves(out))
        self.refresh_ticks_sum += refresh
        return host

    def _embed_plan(self, n_arr, inject):
        """Turn the tick's text-carrying submissions into the router's
        injection arrays: ``feat`` (S, M, F) f32 with NaN rows meaning
        "simulate from the bank", ``labels`` (S, M) int32 with -1 meaning
        "draw". Texts are embedded in ONE batched encoder call
        (:func:`repro.embed.bank.embed_texts`) in the bank's
        standardized feature space."""
        from repro.embed.bank import embed_texts
        from repro.embed.encoder import chunks

        cfg = self.cfg
        S, M = cfg.n_shards, cfg.max_arrivals_per_tick
        F = cfg.learner.n_features
        feat = np.full((S, M, F), np.nan, np.float32)
        labels = np.full((S, M), -1, np.int32)
        texted = [(s, w, r) for s, w, r in inject if r.text is not None]
        if texted:
            with timing.span("serve.embed"):
                vecs = embed_texts(
                    cfg.learner.embed, [r.text for _, _, r in texted],
                    cfg.n_classes, F, cfg.learner.class_sep,
                    cfg.learner.hard_sep_scale)
            for (s, w, _), v in zip(texted, vecs):
                feat[s, w] = v
            self.embed_rows_sum += len(texted)
            self.embed_rows_run_sum += sum(
                rows for _, _, rows in
                chunks(len(texted), cfg.learner.embed.batch_size))
        for s, w, r in inject:
            if r.given_label >= 0:
                labels[s, w] = r.given_label
        return feat, labels

    def _absorb(self, out, n_arr, uid_base):
        now = time.monotonic()
        fin = np.asarray(out["fin"])
        uids = np.asarray(out["uid"])
        labels = np.asarray(out["label"])
        votes = np.asarray(out["votes"])
        confs = np.asarray(out["conf"])
        tis = np.asarray(out["tis"])
        for s, w in zip(*np.nonzero(fin)):
            req = self._by_uid.pop((int(s), int(uids[s, w])), None)
            if req is None:
                continue
            req.status = "done"
            req.label = int(labels[s, w])
            req.votes = int(votes[s, w])
            req.conf = float(confs[s, w])
            req.tis_s = float(tis[s, w])
            req.t_answer = now
            req.tick_answer = self.ticks
            self.answered += 1
            self._lat.append(now - req.t_submit)
            self.queue_wait_s_sum += req.t_inject - req.t_submit
            self.answer_ticks_sum += self.ticks - req.tick_inject + 1
            req.event.set()
        drp = np.asarray(out["dropped"])
        if drp.any():
            # device drops come off the TAIL of this tick's injection
            # (unreachable under the capacity throttle; kept for safety)
            for s in range(len(drp)):
                for k in range(int(drp[s])):
                    u = int(uid_base[s]) + int(n_arr[s]) - 1 - k
                    req = self._by_uid.pop((s, u), None)
                    if req is not None:
                        req.status = "dropped"
                        self.dropped += 1
                        req.event.set()
        self._backlog = np.asarray(out["backlog"]).astype(np.int64)
        self._in_flight = int(np.asarray(out["in_flight"]).sum())
        self.t_sim = float(out["t"])
        self.ticks += 1

    async def _tick_loop(self):
        loop = asyncio.get_running_loop()
        while True:
            if not self._pending and not self._by_uid:
                if self._closing:
                    self._drained.set()
                self._work.clear()
                with timing.span("serve.idle"):
                    await self._work.wait()
            t0 = time.monotonic()
            with timing.span("serve.inject"):
                n_arr, uid_base, inject = self._inject_plan()
            out = await loop.run_in_executor(
                None, self._device_tick, n_arr, uid_base, inject)
            with timing.span("serve.absorb"):
                self._absorb(out, n_arr, uid_base)
            if self._closing and not self._pending and not self._by_uid:
                self._drained.set()
            lag = self.tick_interval_s - (time.monotonic() - t0)
            # always yield so request handlers interleave with the loop
            with timing.span("serve.yield"):
                await asyncio.sleep(lag if lag > 0 else 0)

    # ------------------------------------------------------------------
    # HTTP surface
    # ------------------------------------------------------------------
    async def _handle(self, reader, writer):
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                parts = line.decode("latin-1").strip().split()
                if len(parts) != 3:
                    break
                method, path, version = parts
                headers = {}
                truncated = False
                while True:
                    h = await reader.readline()
                    if h == b"":
                        truncated = True   # EOF mid-headers: the client
                        break              # vanished; don't route a half
                    if h in (b"\r\n", b"\n"):   # request as an empty POST
                        break
                    k, _, v = h.decode("latin-1").partition(":")
                    headers[k.strip().lower()] = v.strip()
                if truncated:
                    break
                n = int(headers.get("content-length") or 0)
                body = await reader.readexactly(n) if n else b""
                status, obj = await self._route(method, path, body)
                keep = headers.get(
                    "connection",
                    "keep-alive" if version == "HTTP/1.1" else "close",
                ).lower() != "close"
                data = json.dumps(obj).encode()
                writer.write((
                    f"HTTP/1.1 {status} {_REASON.get(status, 'OK')}\r\n"
                    "Content-Type: application/json\r\n"
                    f"Content-Length: {len(data)}\r\n"
                    f"Connection: {'keep-alive' if keep else 'close'}\r\n"
                    "\r\n").encode() + data)
                await writer.drain()
                if not keep:
                    break
        except (ConnectionResetError, BrokenPipeError,
                asyncio.IncompleteReadError):
            pass    # abrupt client disconnect; task lifecycle unaffected
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    async def _route(self, method, path, body):
        if method == "POST" and path == "/tasks":
            return await self._post_task(body)
        if method == "GET" and path.startswith("/labels/"):
            return self._get_label(path[len("/labels/"):])
        if method == "GET" and path == "/healthz":
            return 200, dict(ok=not self._closing, ticks=self.ticks)
        if method == "GET" and path == "/stats":
            return 200, self.stats()
        if method == "POST" and path == "/shutdown":
            asyncio.get_running_loop().create_task(self.close())
            return 200, dict(ok=True, draining=bool(self._by_uid
                                                    or self._pending))
        return 404, dict(error=f"no route {method} {path}")

    async def _post_task(self, body):
        try:
            payload = json.loads(body) if body else {}
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, json.JSONDecodeError) as e:
            return 400, dict(error=str(e))
        text = payload.get("text")
        label = payload.get("label", -1)
        if text is not None and not isinstance(text, str):
            return 400, dict(error='"text" must be a string')
        if not isinstance(label, int) or isinstance(label, bool) \
                or not -1 <= label < self.cfg.n_classes:
            return 400, dict(
                error=f'"label" must be an int in [0, {self.cfg.n_classes})'
                      ' or -1')
        if not self._lm and (text is not None or label >= 0):
            return 400, dict(
                error='"text"/"label" need an LM scenario '
                      '(features.kind="lm"); this server runs '
                      f'"{self.cfg.learner.feature_kind}" features')
        if self._closing:
            return 503, dict(error="shutting down")
        if len(self._pending) >= self.max_pending:
            self.rejected += 1
            return 429, dict(error="admission queue full")
        req = _Req(rid=self._next_rid, event=asyncio.Event(),
                   t_submit=time.monotonic(), text=text, given_label=label)
        self._next_rid += 1
        self._reqs[req.rid] = req
        self._pending.append(req)
        self.submitted += 1
        self._work.set()
        if payload.get("wait"):
            timeout = float(payload.get("timeout_s",
                                        self.request_timeout_s))
            try:
                await asyncio.wait_for(req.event.wait(), timeout)
            except asyncio.TimeoutError:
                return 202, req.to_json()
        return (200 if req.status == "done" else 202), req.to_json()

    def _get_label(self, rid_s):
        try:
            rid = int(rid_s)
        except ValueError:
            return 400, dict(error=f"bad id {rid_s!r}")
        req = self._reqs.get(rid)
        if req is None:
            return 404, dict(error=f"unknown id {rid}")
        return 200, req.to_json()

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        lat = np.asarray(self._lat) if self._lat else np.zeros((0,))
        in_system = len(self._by_uid)
        s = dict(
            submitted=self.submitted, answered=self.answered,
            pending=len(self._pending), in_system=in_system,
            dropped=self.dropped, rejected=self.rejected,
            shutdown_unanswered=self.shutdown_unanswered,
            ticks=self.ticks, t_sim=self.t_sim,
            conservation=(self.submitted == self.answered
                          + len(self._pending) + in_system + self.dropped
                          + self.shutdown_unanswered),
            p50_latency_s=float(np.percentile(lat, 50)) if lat.size else None,
            p95_latency_s=float(np.percentile(lat, 95)) if lat.size else None,
            queue_wait_s_sum=self.queue_wait_s_sum,
            answer_ticks_sum=self.answer_ticks_sum,
            tick_out_buffers_sum=self.tick_out_buffers_sum,
            refresh_ticks_sum=self.refresh_ticks_sum,
            embed_rows_sum=self.embed_rows_sum,
            embed_rows_run_sum=self.embed_rows_run_sum,
            timing=[row for row in timing.summary()
                    if row["name"] in ("serve.tick", "serve.embed")],
        )
        return s


class ServeClient:
    """Minimal keep-alive asyncio client for :class:`LabelServer` (what
    the tests and ``benchmarks/bench_serve.py`` drive load with)."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._reader = self._writer = None

    async def connect(self):
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        return self

    async def aclose(self):
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except Exception:
                pass
            self._writer = None

    async def request(self, method: str, path: str, obj=None):
        if self._writer is None:
            await self.connect()
        body = json.dumps(obj).encode() if obj is not None else b""
        self._writer.write((
            f"{method} {path} HTTP/1.1\r\n"
            f"Host: {self.host}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await self._writer.drain()
        status_line = await self._reader.readline()
        if not status_line:
            raise ConnectionResetError("server closed connection")
        status = int(status_line.split()[1])
        n, keep = 0, True
        while True:
            h = await self._reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            k = k.strip().lower()
            if k == "content-length":
                n = int(v)
            elif k == "connection":
                keep = v.strip().lower() != "close"
        data = await self._reader.readexactly(n) if n else b""
        if not keep:
            await self.aclose()
        return status, (json.loads(data) if data else None)

    async def submit(self, *, wait: bool = False, timeout_s: float = None,
                     text: str = None, label: int = None):
        obj = {"wait": wait}
        if timeout_s is not None:
            obj["timeout_s"] = timeout_s
        if text is not None:
            obj["text"] = text
        if label is not None:
            obj["label"] = label
        return await self.request("POST", "/tasks", obj)

    async def label(self, rid: int):
        return await self.request("GET", f"/labels/{rid}")

    async def stats(self):
        return (await self.request("GET", "/stats"))[1]

    async def shutdown(self):
        return await self.request("POST", "/shutdown", {})
