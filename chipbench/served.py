"""One run of a served cell: the label server under generated load.

The server (``repro.serving.server.LabelServer``) runs in this process's
event loop; the load generator runs in a child process
(``loadgen.py``). The sequence:

1. build the server for the configuration, start it and warm it up: for
   text cells every count of texts a tick may embed, then a few waited
   HTTP submissions (this compiles, or loads from the compile cache, every
   program the window runs);
2. start the generator, let it open its connections, and start the window
   on a shared ``time.monotonic`` instant;
3. after ``seconds`` close the window; the generator keeps the load on for
   a short tail and then waits for every answer. Every serve tick from
   the close on is recorded with its pre- and post-tick state for the
   reference comparison;
4. read the device's memory peak, shut the server down, free its state,
   and run the comparisons of ``check.py``.

``TickRecorder`` stands in for ``repro.labelstream.router.serve_tick``
(the server looks it up at each call). It passes every call through,
keeps each tick's host copy of the output (which the server fetched
anyway), and from the close on also keeps the state around each tick.
"""
from __future__ import annotations

import asyncio
import contextlib
import functools
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


class TickRecorder:
    """Stands in for ``serve_tick``: see the module docstring. ``fault``
    (tests only) wraps the real tick from the window on."""

    def __init__(self, real, max_checked: int = 96):
        self.real = real
        self.outs: list = []
        self.checked: list = []
        self.check = False
        self.max_checked = max_checked
        self.fault = None           # a test's broken tick: f(tick_fn, ...)
        self.armed = False          # the fault acts from the window on
        self.annotate = False
        self._pre = None

    def __call__(self, cfg, state, n_arr, uid_base, feat=None, labels=None):
        import jax

        snap = self.check and len(self.checked) < self.max_checked
        if snap and self._pre is None:
            self._pre = jax.device_get(state)
        tick = self.real if self.fault is None or not self.armed \
            else self.fault(self.real)
        with _span("serve_tick.dispatch", self.annotate):
            state, out = tick(cfg, state, n_arr, uid_base, feat=feat,
                              labels=labels)
        with _span("serve_tick.fetch", self.annotate):
            out = jax.device_get(out)
        self.outs.append(out)
        if snap:
            post = jax.device_get(state)
            self.checked.append(dict(
                pre=self._pre, n_arr=np.asarray(n_arr),
                uid_base=np.asarray(uid_base),
                feat=None if feat is None else np.asarray(feat),
                labels=None if labels is None else np.asarray(labels),
                out=out, post=post))
            self._pre = post
        return state, out


class EmbedRecorder:
    """Stands in for ``repro.embed.bank.embed_texts`` and the ``encode``
    it calls: passes every call through and, while ``armed`` (the
    window), keeps each call's texts with the encoder's features for
    them, before the bank's standardization."""

    def __init__(self, bank):
        self.bank = bank
        self.real_embed, self.real_encode = bank.embed_texts, bank.encode
        self.calls: list = []
        self.armed = False
        self._texts = None

    def embed_texts(self, ec, texts, *a, **kw):
        self._texts = list(texts) if self.armed else None
        try:
            return self.real_embed(ec, texts, *a, **kw)
        finally:
            self._texts = None

    def encode(self, ec, tokens, lengths, n_features, **kw):
        out = self.real_encode(ec, tokens, lengths, n_features, **kw)
        if self._texts is not None:
            self.calls.append((self._texts, out))
        return out

    def install(self):
        self.bank.embed_texts, self.bank.encode = self.embed_texts, self.encode

    def remove(self):
        self.bank.embed_texts, self.bank.encode = self.real_embed, \
            self.real_encode


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _annotate(fn, name: str):
    def wrapped(*a, **kw):
        with _span(name, True):
            return fn(*a, **kw)
    return wrapped


class CompileCounter:
    """Counts executables built (compiled or loaded from the cache) while
    ``on``: none should be built inside the window. One listener per
    process, reset by each run."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _one = None

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @classmethod
    def reset(cls):
        if cls._one is None:
            cls._one = cls()
        cls._one.on, cls._one.n = False, 0
        return cls._one

    def _event(self, name, secs, **kw):
        if self.on and name == self.EVENT:
            self.n += 1


def server_spec(cfg: dict, overrides: dict | None = None):
    from repro.scenarios import get_scenario
    ov = dict(cfg["overrides"])
    ov.update(overrides or {})
    return get_scenario(cfg["scenario"], ov)


def check_semantics(spec, ref: dict):
    """Fail loudly when the program's configuration no longer states what
    the reference's parameters say (the reference would then judge
    another deployment)."""
    from repro.scenarios.compile import to_serve_config
    c = to_serve_config(spec)
    pol, L = c.policy, c.learner
    got = dict(
        n_shards=c.n_shards, pool_size=c.pool_size, window=c.window,
        backlog=c.backlog, max_arrivals=c.max_arrivals_per_tick,
        n_classes=c.n_classes, votes_cap=pol.votes_cap,
        conf_threshold=pol.conf_threshold, min_votes=pol.min_votes,
        adaptive=pol.adaptive, est_prior_acc=c.est_prior_acc,
        est_prior_n=c.est_prior_n, p_hard=c.p_hard, hard_scale=c.hard_scale,
        feature_kind=L.feature_kind, learner=L.enabled,
        admission=c.routing.admission, routing=c.routing.enabled,
        refresh_every=c.refresh_every, pm_l=c.pm_l,
        n_devices=c.sharding.n_devices, batch_replay=c.batch_replay)
    if L.enabled:
        got.update(known_threshold=L.known_threshold,
                   min_votes_known=L.min_votes_known,
                   learner_prior_scale=L.prior_scale,
                   learner_ramp_n=L.ramp_n,
                   learner_train_crowd_only=L.train_crowd_only,
                   learner_buffer=L.buffer, learner_fit_every=L.fit_every,
                   learner_fit_steps=L.fit_steps, learner_lr=L.lr,
                   learner_l2=L.l2)
    want = {k: ref[k] for k in got if k in ref}
    want["pm_l"] = float(ref.get("pm_l") or "inf")
    diff = {k: (got[k], want.get(k)) for k in got if got[k] != want.get(k)}
    if diff:
        raise ValueError(f"the program's configuration differs from the "
                         f"reference's parameters: {diff}")


async def _warm(host, port, n: int, payload):
    """``n`` waited submissions over HTTP, two at a time."""
    from loadgen import Conn
    conns = [await Conn(host, port).open() for _ in range(2)]

    async def go(c, k):
        for _ in range(k):
            st, ans = await c.post("/tasks", payload())
            if st != 200 or ans.get("status") != "done":
                raise RuntimeError(f"warm-up request failed: {st} {ans}")

    await asyncio.gather(*[go(c, n // 2) for c in conns])
    for c in conns:
        c.close()


def _warm_embed(cfg, n_max: int):
    """The text path runs eager array operations whose shapes follow the
    number of texts a tick embeds: run every count up to ``n_max`` once,
    so none of them compiles in the window."""
    import repro.embed.bank as bank
    L = cfg.learner
    for n in range(1, n_max + 1):
        np.asarray(bank.embed_texts(L.embed, ["warm up"] * n, cfg.n_classes,
                                    L.n_features, L.class_sep,
                                    L.hard_sep_scale))


async def _sample_occupancy(server, t0, out: list, lag: list,
                            every_s: float = 0.25):
    """(time, pending + in system) every ``every_s`` through the window:
    a load the system sustains leaves it flat. ``lag`` keeps the longest
    time the server's event loop woke this sampler late, and when."""
    while True:
        out.append((time.monotonic() - t0,
                    len(server._pending) + len(server._by_uid)))
        t = time.monotonic()
        await asyncio.sleep(every_s)
        late = time.monotonic() - t - every_s
        if late > lag[1]:
            lag[:] = [t + every_s - t0, late]


async def _session(spec, traffic_file, *, seed, seconds, tail_s, drain_s,
                   trace_s, recorder, t_proc0, n_warm, workdir, armed=()):
    import jax
    from repro.obs import timing
    from repro.serving.server import LabelServer

    from loadgen import Payloads

    traffic = json.loads(pathlib.Path(traffic_file).read_text())
    server = LabelServer(spec, seed=seed % (2 ** 31 - 1))
    if recorder.annotate:
        for name in ("_inject_plan", "_absorb", "_embed_plan"):
            setattr(server, name, _annotate(getattr(server, name),
                                            name.strip("_")))
    await server.start()
    counter = CompileCounter.reset()
    if traffic.get("text"):
        # builds the embedding bank, then every texts-per-tick count
        await asyncio.get_running_loop().run_in_executor(
            None, _warm_embed, server.cfg,
            traffic["text"]["warm_max_per_tick"])
    await _warm(server.host, server.port, n_warm,
                Payloads(traffic, seed ^ 0x3A3A, spec.n_classes, 600.0))

    job = dict(host=server.host, port=server.port,
               traffic_file=str(traffic_file), seed=seed, seconds=seconds,
               tail_s=tail_s, drain_s=drain_s, n_classes=spec.n_classes,
               result_file=str(workdir / "records.json"))
    (workdir / "job.json").write_text(json.dumps(job))
    gen = await asyncio.create_subprocess_exec(
        sys.executable, str(HERE / "loadgen.py"), str(workdir / "job.json"),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE)
    try:
        line = await gen.stdout.readline()
        if line.strip() != b"ready":
            raise RuntimeError(f"load generator did not start: {line!r}")
        t0 = time.monotonic() + 0.05
        gen.stdin.write(f"go {t0!r}\n".encode())
        await gen.stdin.drain()
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        ent = timing.entries()
        mark0 = dict(t=time.monotonic(), ticks=server.ticks,
                     tick_i=len(ent.get("serve.tick", [])),
                     embed_i=len(ent.get("serve.embed", [])))
        counter.on = True
        for a in armed:
            a.armed = True
        occupancy: list = []
        server_lag = [0.0, 0.0]
        loop = asyncio.get_running_loop()
        sampler = loop.create_task(_sample_occupancy(server, t0, occupancy,
                                                     server_lag))
        mark1: dict = {}

        def close_window():
            counter.on = False
            sampler.cancel()
            ent = timing.entries()
            mark1.update(t=time.monotonic(), ticks=server.ticks,
                         tick_i=len(ent.get("serve.tick", [])),
                         embed_i=len(ent.get("serve.embed", [])))
            recorder.check = True
            for a in armed[1:]:
                a.armed = False

        loop.call_at(t0 + seconds, close_window)
        trace_dir = None
        if trace_s > 0:
            await asyncio.sleep(max(0.0, t0 + 0.3 * seconds
                                    - time.monotonic()))
            trace_dir = tempfile.mkdtemp(prefix="trace", dir=workdir)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans and runtime only
            await loop.run_in_executor(None, functools.partial(
                jax.profiler.start_trace, trace_dir,
                profiler_options=opts))
            tr0 = time.monotonic()
            await asyncio.sleep(trace_s)
            tr1 = time.monotonic()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        line = await gen.stdout.readline()
        await gen.wait()
        if line.strip() != b"done":
            raise RuntimeError("load generator failed")
    finally:
        if gen.returncode is None:
            gen.kill()
            await gen.wait()
    recorder.check = False
    # let the last answers settle, then read the ledger
    for _ in range(200):
        if not server._by_uid and not server._pending:
            break
        await asyncio.sleep(0.01)
    stats = server.stats()
    kind = jax.devices()[0].device_kind
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices())
    await server.close()
    if server.error is not None:
        raise RuntimeError("serve tick loop failed") from server.error
    ent = timing.entries()
    rec = json.loads((workdir / "records.json").read_text())
    run = dict(
        t0=t0, seconds=seconds, setup_s=t0 - t_proc0,
        window_s=mark1["t"] - mark0["t"],
        ticks=mark1["ticks"] - mark0["ticks"],
        tick_s=ent.get("serve.tick", [])[mark0["tick_i"]:mark1["tick_i"]],
        embed_s=ent.get("serve.embed", [])[mark0["embed_i"]:mark1["embed_i"]],
        compiles_in_window=counter.n, occupancy=occupancy,
        server_loop_lag=server_lag, device_kind=kind,
        memory_peak_bytes=mem, stats=stats,
        records=rec, reqs=dict(server._reqs), traffic=traffic)
    if trace_dir is not None:
        run.update(trace_dir=trace_dir, trace_window_s=tr1 - tr0)
    server.state = None
    return run


def run_cell(cfg: dict, traffic_file, *, seed: int, seconds: float,
             trace: bool, t_proc0: float, tail_s=0.5,
             drain_s=60.0, trace_s=None, n_warm=16, fault=None,
             workdir=None) -> dict:
    """Serve the configuration under the traffic for one window and return
    the run's record (see ``_session``), with the reference comparison's
    numbers under ``checks``."""
    import repro.embed.bank as bank
    import repro.labelstream.router as router

    import check

    server_seed = seed % (2 ** 31 - 1)
    spec = server_spec(cfg, {k: server_seed
                             for k in cfg.get("seed_overrides", ())})
    ref = cfg["reference"]
    check_semantics(spec, ref)
    recorder = TickRecorder(router.serve_tick)
    recorder.fault = fault
    recorder.annotate = trace
    embeds = EmbedRecorder(bank)
    own_dir = workdir is None
    workdir = pathlib.Path(workdir or tempfile.mkdtemp(prefix="chipbench"))
    router.serve_tick = recorder
    embeds.install()
    try:
        if trace_s is None:
            trace_s = min(2.0, 0.4 * seconds) if trace else 0.0
        run = asyncio.run(_session(
            spec, traffic_file, seed=seed, seconds=seconds, tail_s=tail_s,
            drain_s=drain_s, trace_s=trace_s, recorder=recorder,
            t_proc0=t_proc0, n_warm=n_warm, workdir=workdir,
            armed=(recorder, embeds)))
        if trace:
            run["trace"] = reduce_trace(run)
    finally:
        router.serve_tick = recorder.real
        embeds.remove()
        if own_dir:
            shutil.rmtree(workdir, ignore_errors=True)
    free_program()
    eps = cfg["decision_eps"]
    numbers = check.check_answers(ref, run["records"], run["reqs"],
                                  recorder.outs, run["stats"])
    numbers.update(check.check_ticks(ref, recorder.checked, eps))
    if "encoder" in cfg:
        sample = check.embed_sample(cfg["encoder"], embeds.calls, seed)
        numbers.update(check.check_embeddings(cfg["encoder"], sample,
                                              server_seed))
        run["embed_sample"] = sample
    run["checks"] = numbers
    run["checked"] = recorder.checked
    run["server_seed"] = server_seed
    if "encoder" in cfg:
        b = cfg["encoder"]["batch_size"]
        rows = sum(len(t) for t, _ in embeds.calls)
        batches = sum(-(-len(t) // b) for t, _ in embeds.calls)
        run.update(encoder=cfg["encoder"],
                   embed_rows_per_batch=rows / batches if batches else None)
    return run


def free_program():
    """Drop the program's cached device arrays (encoder weights, the
    embedding bank) before the reference runs on the chip."""
    import repro.embed.bank as bank
    import repro.embed.encoder as encoder
    for fn in (bank.embedding_bank, encoder.model_params,
               encoder.projection):
        fn.cache_clear()


def reduce_trace(run: dict):
    import trace_reduce
    path = trace_reduce.find_xplane(run["trace_dir"])
    if path is None:
        return None
    red = trace_reduce.reduce_file(path, run["trace_window_s"])
    shutil.rmtree(run["trace_dir"], ignore_errors=True)
    return red
