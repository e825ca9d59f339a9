"""Live serving front end (repro.serving.server): conservation under
concurrent clients, request timeouts, abrupt disconnects, graceful
shutdown, and bitwise determinism of the serve tick under a fixed seed.

All HTTP tests share one scenario config so the serve tick compiles once
per test session; each test spins up a fresh in-process server on an
ephemeral loopback port (no sockets leak across tests)."""
import asyncio
import json

import numpy as np
import pytest


def _spec():
    from repro import scenarios
    return scenarios.get_scenario("serve_default")


def _server(**kw):
    from repro.serving.server import LabelServer
    kw.setdefault("tick_interval_s", 0.0)
    return LabelServer(_spec(), seed=0, port=0, **kw)


def test_conservation_under_concurrent_clients():
    """Every submission from racing keep-alive clients answers, and the
    ledger balances: submitted == answered + pending + in-system +
    dropped + shutdown, with zero device drops (capacity throttling)."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()
        n_clients, per_client = 6, 5

        async def client(i):
            c = await ServeClient(srv.host, srv.port).connect()
            out = []
            for _ in range(per_client):
                out.append(await c.submit(wait=True, timeout_s=60.0))
            await c.aclose()
            return out

        results = await asyncio.gather(
            *[client(i) for i in range(n_clients)])
        stats = srv.stats()
        await srv.close()
        return results, stats

    results, stats = asyncio.run(main())
    flat = [r for out in results for r in out]
    assert all(s == 200 and r["status"] == "done" for s, r in flat), flat
    n = len(flat)
    assert stats["submitted"] == n
    assert stats["answered"] == n
    assert stats["dropped"] == 0
    assert stats["conservation"] is True
    # answered requests carry the full label payload + wall-clock latency
    for _, r in flat:
        assert r["label"] in (0, 1)
        assert r["votes"] >= 1
        assert r["latency_s"] >= 0.0


def _serve_waited(n_clients, per_client):
    """Serve ``n_clients`` racing keep-alive clients, each making
    ``per_client`` waited submissions; returns the closed server and its
    final stats."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()

        async def client():
            c = await ServeClient(srv.host, srv.port).connect()
            for _ in range(per_client):
                status, r = await c.submit(wait=True, timeout_s=60.0)
                assert status == 200 and r["status"] == "done", (status, r)
            await c.aclose()

        await asyncio.gather(*[client() for _ in range(n_clients)])
        stats = srv.stats()
        await srv.close()
        return srv, stats

    return asyncio.run(main())


def test_request_counters_and_tick_spans():
    """Each answered request carries its injection time and the ticks at
    injection and answer; ``/stats`` sums them; every ``serve.tick`` span
    holds exactly one ``serve.dispatch`` (the router's call)."""
    from repro.obs import timing

    timing.clear()
    srv, stats = _serve_waited(4, 6)
    reqs = list(srv._reqs.values())
    assert len(reqs) == stats["answered"] == 24
    for r in reqs:
        assert r.status == "done"
        assert r.t_submit <= r.t_inject <= r.t_answer
        assert 0 <= r.tick_inject <= r.tick_answer < srv.ticks
    assert stats["queue_wait_s_sum"] == pytest.approx(
        sum(r.t_inject - r.t_submit for r in reqs))
    assert stats["answer_ticks_sum"] == sum(
        r.tick_answer - r.tick_inject + 1 for r in reqs)

    ticks = timing.spans("serve.tick")
    dispatch = timing.spans("serve.dispatch")
    assert len(ticks) == srv.ticks == len(dispatch)
    for t in ticks:
        kids = [d for d in dispatch if d.parent == t.id]
        assert len(kids) == 1
        assert t.start <= kids[0].start <= kids[0].end <= t.end
    for name in ("serve.inject", "serve.absorb"):
        assert len(timing.spans(name)) == srv.ticks, name
    timing.clear()


def test_tick_fetch_moves_one_buffer_per_tick():
    """The served tick fetches one packed device buffer per tick."""
    srv, stats = _serve_waited(3, 4)
    assert stats["ticks"] > 0
    assert stats["tick_out_buffers_sum"] == stats["ticks"]


def test_refresh_ticks_have_their_span_and_counter():
    """With the offline Dawid-Skene refresh every 3 ticks, each tick whose
    step runs it (every third, counted from the first) sits inside a
    ``serve.refresh_tick`` span and is counted in ``refresh_ticks_sum``;
    with the refresh off there are none."""
    from repro import scenarios
    from repro.obs import timing
    from repro.serving.server import LabelServer, ServeClient

    async def main(every):
        spec = scenarios.get_scenario(
            "serve_default", {"policy.learner.refresh_every": every})
        srv = await LabelServer(spec, seed=0, port=0,
                                tick_interval_s=0.0).start()
        c = await ServeClient(srv.host, srv.port).connect()
        for _ in range(4):
            status, r = await c.submit(wait=True, timeout_s=60.0)
            assert status == 200 and r["status"] == "done", (status, r)
        await c.aclose()
        stats = srv.stats()
        await srv.close()
        return srv, stats

    timing.clear()
    srv, stats = asyncio.run(main(3))
    assert stats["refresh_ticks_sum"] == srv.ticks // 3 > 0
    ticks = timing.spans("serve.tick")
    refresh = timing.spans("serve.refresh_tick")
    assert len(refresh) == stats["refresh_ticks_sum"]
    for i, t in enumerate(ticks):
        inside = [r for r in refresh if t.parent == r.id]
        assert len(inside) == (i % 3 == 2), i
        for r in inside:
            assert r.start <= t.start <= t.end <= r.end
    timing.clear()
    _, stats = asyncio.run(main(0))
    assert stats["refresh_ticks_sum"] == 0
    assert timing.spans("serve.refresh_tick") == []
    timing.clear()


def test_latency_percentiles_cover_the_most_recent_answers(monkeypatch):
    """``/stats`` percentiles read a bounded window of the latest
    answers, so a long-running server's latency store stops growing."""
    from repro.serving import server as server_mod

    monkeypatch.setattr(server_mod, "LATENCY_WINDOW", 4)
    srv, stats = _serve_waited(2, 5)
    assert stats["answered"] == 10
    assert len(srv._lat) == 4
    reqs = sorted(srv._reqs.values(), key=lambda r: r.t_answer)
    latest = {r.t_answer - r.t_submit for r in reqs
              if r.t_answer >= reqs[-4].t_answer}
    assert set(srv._lat) <= latest
    assert stats["p50_latency_s"] == pytest.approx(
        float(np.percentile(list(srv._lat), 50)))


def test_request_timeout_keeps_task_in_system():
    """A wait=True submission whose long-poll times out gets 202 — but
    only the HTTP wait dies; the task stays in the system, finalizes on
    a later tick, and is retrievable via GET /labels/<id>."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()
        c = await ServeClient(srv.host, srv.port).connect()
        status, r = await c.submit(wait=True, timeout_s=0.0)
        assert status == 202, (status, r)
        assert r["status"] in ("pending", "queued"), r
        rid = r["id"]
        for _ in range(400):
            status, r = await c.label(rid)
            if r["status"] == "done":
                break
            await asyncio.sleep(0.02)
        stats = srv.stats()
        await c.aclose()
        await srv.close()
        return r, stats

    r, stats = asyncio.run(main())
    assert r["status"] == "done", r
    assert stats["answered"] == stats["submitted"] == 1
    assert stats["conservation"] is True


def test_abrupt_client_disconnect():
    """A client that submits and vanishes before reading the response
    must not wedge the server or leak its task: the submission still
    finalizes, later clients are served, conservation holds."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()

        # full request, socket torn down before the response is read
        reader, writer = await asyncio.open_connection(srv.host, srv.port)
        body = json.dumps({"wait": True, "timeout_s": 60.0}).encode()
        writer.write((f"POST /tasks HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        writer.close()

        # half a request, then gone mid-headers
        reader, writer = await asyncio.open_connection(srv.host, srv.port)
        writer.write(b"POST /tasks HTTP/1.1\r\nContent-Le")
        await writer.drain()
        writer.close()

        # a well-behaved client is still served
        c = await ServeClient(srv.host, srv.port).connect()
        status, r = await c.submit(wait=True, timeout_s=60.0)
        assert status == 200 and r["status"] == "done", (status, r)
        # the orphaned submission drains too
        for _ in range(400):
            stats = srv.stats()
            if stats["answered"] == stats["submitted"]:
                break
            await asyncio.sleep(0.02)
        await c.aclose()
        await srv.close()
        return stats

    stats = asyncio.run(main())
    # the torn-down half-request never became a submission; the complete
    # one did and was answered despite the dead socket
    assert stats["submitted"] == 2
    assert stats["answered"] == 2
    assert stats["conservation"] is True


def test_graceful_shutdown_resolves_stragglers():
    """close(drain=True) answers what it can inside the drain window and
    resolves the rest as status='shutdown' — nothing is left hanging and
    the conservation ledger still balances."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()
        c = await ServeClient(srv.host, srv.port).connect()
        rids = []
        for _ in range(8):
            status, r = await c.submit(wait=False)
            assert status in (200, 202)
            rids.append(r["id"])
        await c.aclose()
        await srv.close(drain=True)
        states = [srv._reqs[rid].status for rid in rids]
        return states, srv.stats()

    states, stats = asyncio.run(main())
    assert all(s in ("done", "shutdown") for s in states), states
    assert stats["conservation"] is True
    assert stats["answered"] + stats["shutdown_unanswered"] == 8
    # after close, new submissions are refused (server socket is down)
    assert stats["pending"] == 0 and stats["in_system"] == 0


def test_rejects_bad_requests():
    """400 on malformed JSON, 404 on unknown routes, 404 on unknown ids;
    none of these perturb the ledger."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()
        c = await ServeClient(srv.host, srv.port).connect()
        out = {}
        # malformed JSON body
        reader, writer = await asyncio.open_connection(srv.host, srv.port)
        writer.write(b"POST /tasks HTTP/1.1\r\nHost: t\r\n"
                     b"Content-Length: 5\r\n\r\n{oops")
        await writer.drain()
        line = await reader.readline()
        out["bad_json"] = int(line.split()[1])
        writer.close()
        out["no_route"] = (await c.request("GET", "/nope"))[0]
        out["bad_id"] = (await c.label(99))[0]
        stats = srv.stats()
        await c.aclose()
        await srv.close()
        return out, stats

    out, stats = asyncio.run(main())
    assert out == {"bad_json": 400, "no_route": 404, "bad_id": 404}
    assert stats["submitted"] == 0 and stats["conservation"] is True


def test_lm_text_submission_embeds_and_answers():
    """On an LM scenario, a submission carrying real text (plus a known
    label) embeds through the encoder and injects into the tick — it
    answers like any other task, the embed path shows up in the timing
    stats, and plain no-text submissions still work side by side. On a
    Gaussian scenario the same body is a 400."""
    from repro import scenarios
    from repro.serving.server import LabelServer, ServeClient

    async def main():
        srv = await LabelServer(scenarios.get_scenario("lm_stream"),
                                seed=0, port=0,
                                tick_interval_s=0.0).start()
        c = await ServeClient(srv.host, srv.port).connect()
        texted = await c.submit(wait=True, timeout_s=60.0,
                                text="the quick brown fox", label=1)
        plain = await c.submit(wait=True, timeout_s=60.0)
        stats = srv.stats()
        await c.aclose()
        await srv.close()
        return texted, plain, stats

    (st, rt), (sp, rp), stats = asyncio.run(main())
    assert st == 200 and rt["status"] == "done", (st, rt)
    assert sp == 200 and rp["status"] == "done", (sp, rp)
    assert stats["answered"] == stats["submitted"] == 2
    assert stats["conservation"] is True
    timed = {row["name"] for row in stats["timing"]}
    assert "serve.embed" in timed, timed


def test_lm_embed_row_counters_in_stats():
    """``/stats`` counts the texts the server embedded and the rows the
    encoder's programs ran for them: one text a tick (each waited on)
    runs the smallest bucket."""
    from repro import scenarios
    from repro.embed.encoder import bucket_rows
    from repro.serving.server import LabelServer, ServeClient

    texts = ["the quick brown fox", "a lazy dog", "jumps over"]

    async def main():
        spec = scenarios.get_scenario("lm_stream")
        srv = await LabelServer(spec, seed=0, port=0,
                                tick_interval_s=0.0).start()
        c = await ServeClient(srv.host, srv.port).connect()
        for i, t in enumerate(texts):
            await c.submit(wait=True, timeout_s=60.0, text=t, label=i % 2)
        stats = await c.stats()
        await c.aclose()
        await srv.close()
        return spec.embed.batch_size, stats

    B, stats = asyncio.run(main())
    assert stats["embed_rows_sum"] == len(texts)
    assert stats["embed_rows_run_sum"] == len(texts) * bucket_rows(1, B)


def test_text_submission_rejected_on_gaussian_scenario():
    """serve_default draws Gaussian features in the tick — there is no
    encoder to route text through, so text/label bodies are a 400 that
    names the feature kind and never enters the ledger."""
    from repro.serving.server import ServeClient

    async def main():
        srv = await _server().start()
        c = await ServeClient(srv.host, srv.port).connect()
        status, r = await c.submit(text="hello", label=0)
        stats = srv.stats()
        await c.aclose()
        await srv.close()
        return status, r, stats

    status, r, stats = asyncio.run(main())
    assert status == 400, (status, r)
    assert "lm" in r["error"], r
    assert stats["submitted"] == 0 and stats["conservation"] is True


def test_serve_tick_deterministic_fixed_seed():
    """Two serve runs with the same seed and the same injection schedule
    produce bitwise-identical finalization streams and end states — the
    live server's tick stream is replayable."""
    import jax
    from repro import scenarios
    from repro.labelstream.router import serve_init, serve_tick

    cfg = scenarios.to_serve_config(_spec())
    S = cfg.n_shards
    rng = np.random.default_rng(123)
    # a fixed, bursty injection schedule (well under backlog capacity)
    schedule = rng.integers(0, 3, size=(30, S)).astype(np.int32)

    def run_once():
        state = serve_init(cfg, seed=7)
        uid_base = np.zeros((S,), np.int32)
        outs = []
        for n_arr in schedule:
            state, out = serve_tick(cfg, state, n_arr, uid_base)
            uid_base = uid_base + n_arr
            outs.append(jax.device_get(out))
        return outs, jax.device_get(state)

    outs_a, state_a = run_once()
    outs_b, state_b = run_once()
    for oa, ob in zip(outs_a, outs_b):
        assert sorted(oa) == sorted(ob)
        for k in oa:
            np.testing.assert_array_equal(np.asarray(oa[k]),
                                          np.asarray(ob[k]), err_msg=k)
    la, ta = jax.tree_util.tree_flatten(state_a)
    lb, tb = jax.tree_util.tree_flatten(state_b)
    assert ta == tb
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # the finalization stream actually finalized something
    total_fin = sum(int(np.asarray(o["fin"]).sum()) for o in outs_a)
    assert total_fin > 0


@pytest.mark.parametrize("name", ["serve_default", "lm_stream"])
def test_packed_tick_out_matches_unpacked_fields(name):
    """``serve_tick``'s packed output unpacks, key by key, to the fields
    the tick computes before packing: same shape, dtype and bytes, with
    one pytree leaf, whether read after ``device_get`` or directly."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro import scenarios
    from repro.labelstream.router import (
        TickOut, _bank_for, _serve_tick_impl, serve_init, serve_tick,
    )

    cfg = scenarios.to_serve_config(scenarios.get_scenario(name))
    S = cfg.n_shards
    kw = {}
    if cfg.learner.feature_kind == "lm":
        # serve_tick's defaults: every injection draws from the bank
        M, F = cfg.max_arrivals_per_tick, cfg.learner.n_features
        kw = dict(feat_in=jnp.full((S, M, F), jnp.nan, jnp.float32),
                  labels_in=jnp.full((S, M), -1, jnp.int32),
                  bank=_bank_for(cfg))
    unpacked = jax.jit(functools.partial(_serve_tick_impl, cfg))
    state, ref_state = serve_init(cfg, seed=0), serve_init(cfg, seed=0)
    base, fin = np.zeros((S,), np.int32), 0
    for i in range(24):
        n = np.asarray([(i + s) % 3 for s in range(S)], np.int32)
        ref_state, ref = unpacked(ref_state, n, base, **kw)
        state, out = serve_tick(cfg, state, n, base)
        base = base + n
        assert isinstance(out, TickOut)
        assert len(jax.tree_util.tree_leaves(out)) == 1
        direct = {k: out[k] for k in out}
        host, ref = jax.device_get(out), jax.device_get(ref)
        assert list(host) == sorted(ref)
        for k, r in ref.items():
            r = np.asarray(r)
            for got in (host[k], direct[k]):
                assert (got.shape, got.dtype) == (r.shape, r.dtype), k
                assert got.tobytes() == r.tobytes(), k
        assert host["fin"].dtype == np.bool_
        fin += int(host["fin"].sum())
    assert fin > 0


def test_packed_tick_out_keeps_nonfinite_floats_bit_exact():
    """NaN (with its payload), infinities and -0.0 survive the bit-cast
    packing, next to bool and int32 fields and a scalar."""
    import jax
    from repro.labelstream.router import _pack_tick_out

    conf = np.array([0.0, np.inf, -np.inf, -0.0, 1.5], np.float32)
    conf[0] = np.frombuffer(np.uint32(0x7FC00123).tobytes(), np.float32)[0]
    fields = dict(fin=np.array([[True, False], [False, True]]), conf=conf,
                  uid=np.array([-2, 2**31 - 1], np.int32),
                  t=np.float32(np.nan))
    out = jax.device_get(jax.jit(_pack_tick_out)(fields))
    assert list(out) == sorted(fields)
    assert len(jax.tree_util.tree_leaves(out)) == 1
    for k, x in fields.items():
        x = np.asarray(x)
        assert (out[k].shape, out[k].dtype) == (x.shape, x.dtype), k
        assert out[k].tobytes() == x.tobytes(), k


def test_tick_loop_failure_stops_server_and_launcher(monkeypatch):
    """A serve tick that raises (a failed compile, a device error) stops
    the server: the waiting request resolves as "shutdown", and the
    launcher's serve loop re-raises, so the process exits non-zero."""
    import argparse

    from repro.labelstream import router
    from repro.launch import serve
    from repro.serving.server import LabelServer, ServeClient

    def broken_tick(*a, **kw):
        raise FloatingPointError("tick failed")

    monkeypatch.setattr(router, "serve_tick", broken_tick)
    started = []
    start = LabelServer.start

    async def recording_start(self):
        started.append(self)
        return await start(self)

    monkeypatch.setattr(LabelServer, "start", recording_start)
    args = argparse.Namespace(scenario="serve_default", seed=0,
                              host="127.0.0.1", port=0,
                              tick_interval_s=0.0)

    async def main():
        loop_task = asyncio.create_task(serve._serve_forever(args))
        while not started or started[0]._server is None:
            await asyncio.sleep(0.01)
        srv = started[0]
        c = await ServeClient(srv.host, srv.port).connect()
        status, r = await c.submit(wait=True, timeout_s=30.0)
        await c.aclose()
        with pytest.raises(RuntimeError, match="tick loop failed") as ei:
            await asyncio.wait_for(loop_task, 30.0)
        return status, r, ei.value, srv.stats()

    status, r, err, stats = asyncio.run(main())
    assert (status, r["status"]) == (202, "shutdown")
    assert isinstance(err.__cause__, FloatingPointError)
    assert stats["conservation"] is True
    assert stats["shutdown_unanswered"] == 1
