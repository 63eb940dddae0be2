"""Set-up traffic, warm-up, and the measured window.

The window is an open loop on one thread: requests are submitted when they
are due, whatever the engine is doing, and the engine is stepped while it
has work. Every request is timed from when it was due. A write is
acknowledged when the ingest batch that journaled and applied it returns;
a query is answered when its query batch returns; a token is produced at
the end of the ``ServeEngine.step`` that appended it.

A mix with streams closes its window once every request due in it is done,
or ``grace_s`` after the last one was due; a request not done by then has
failed, and its latency is its age at the close. A saturated import closes
at the end of the first step that ends past ``seconds``, so its rate is
over whole steps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

from traffic import Plan, Req


@dataclass
class Window:
    t0: float = 0.0                     # perf_counter at the open
    close: float = 0.0                  # seconds after the open
    requests: List[Req] = field(default_factory=list)
    sessions: List = field(default_factory=list)        # acknowledged imports
    step_ends: List[float] = field(default_factory=list)
    decode_lengths: List[List[int]] = field(default_factory=list)  # per step
    prefill_lengths: List[int] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)  # submitted - due


def busy(engine) -> bool:
    return bool(engine.queue or engine.ingest_queue or engine.query_queue
                or any(a is not None for a in engine.active))


def drain(engine, max_steps: int = 100000) -> None:
    engine.run_until_drained(max_steps=max_steps)
    engine.finished.clear()
    engine.query_results.clear()


def write_all(engine, sessions, batch: int) -> None:
    """Set-up writes through the engine's ingest lane, ``batch`` a step."""
    for i in range(0, len(sessions), batch):
        for s in sessions[i:i + batch]:
            engine.submit_session(s)
        drain(engine)


def warm_up(engine, encoder, forest, plan: Plan, mix: dict, cfg: dict) -> None:
    """Compile every shape the window can use, through the engine itself:
    each (rows, tokens) bucket of the encoder, every ingest and query batch
    size the engine can drain, every tree-refresh, browse and index-update
    bucket, and the LM's prefill widths. The fact and root indexes are first
    given room to grow through the window without reaching a new capacity
    (each new one compiles). A mix with ``resident_lm`` admits one short LM request,
    so that the decode cache the deployment keeps beside the memory is
    allocated, and stays so through the window."""
    from repro.core.encoder import bucket
    from repro.kernels import ops
    import jax.numpy as jnp

    serve = cfg["serve"]
    warm = mix["warm"]
    rows = 8
    while rows <= warm["encoder_rows_max"]:
        width = 16
        while width <= serve["encoder_max_tokens"]:
            encoder.inner.encode([" ".join(["a"] * width)] * rows)
            width *= 2
        rows *= 2
    k, dim = forest.config.branching_factor, forest.config.embed_dim
    p = 1
    while p <= warm["refresh_parents_max"]:
        ops.tree_refresh(jnp.zeros((p, k, dim), jnp.float32),
                         jnp.zeros((p, k), jnp.float32), impl=forest.kernel_impl)
        p *= 2
    for n in warm["write_batches"]:
        write_all(engine, [next(plan.warm_sessions) for _ in range(n)], n)
    while (forest.fact_emb.shape[0] - len(forest.facts) < warm.get("index_room_facts", 0)
           or forest.root_index()[0].shape[0] - len(forest.trees)
           < warm.get("index_room_trees", 0)):
        n = serve["max_ingest_batch"]
        write_all(engine, [next(plan.warm_sessions) for _ in range(n)], n)
    for n in warm["query_batches"]:
        for i in range(n):
            engine.submit_query(plan.questions[i % len(plan.questions)])
        drain(engine)
    if warm["query_batches"]:
        # browse scoring of every frontier bucket, and row updates of every
        # bucket at the capacities the indexes now have
        f = 8
        while f <= warm["browse_frontier_max"]:
            kp = 4
            while kp <= k:
                ops.browse_scores(jnp.zeros((f, kp, dim), jnp.float32),
                                  jnp.zeros((f, dim), jnp.float32),
                                  jnp.zeros((f, kp), jnp.float32),
                                  impl=forest.kernel_impl)
                kp *= 2
            f *= 2
        for index in (forest.fact_index_device()[0], forest.root_index_device()[0]):
            u = 1
            while u <= warm["index_update_max"]:
                ops.scatter_normalize_rows(
                    index, jnp.full((u,), index.shape[0], jnp.int32),
                    jnp.zeros((u, dim), jnp.float32))
                u *= 2
    resident = mix.get("resident_lm")
    if resident:
        engine.submit([5] * resident["prompt_tokens"],
                      max_new_tokens=resident["max_new_tokens"])
        drain(engine)
    lm = mix.get("streams", {}).get("lm")
    if lm:
        max_new = lm["max_new_tokens"]
        lo, hi = lm["prompt_tokens"]
        widths = [bucket(lo, 16, serve["max_len"])]
        while widths[-1] < bucket(hi, 16, serve["max_len"]):
            widths.append(widths[-1] * 2)
        room = serve["max_len"] - max_new
        # the first admission takes the path without a merge; each width
        # then joins it through the merge path while it is still decoding
        engine.submit([5] * min(widths[0], room), max_new_tokens=len(widths) + 2)
        engine.step()
        for w in widths:
            engine.submit([5] * min(w, room), max_new_tokens=2)
            engine.step()
        drain(engine)


def run_window(engine, memory, plan: Plan, mix: dict, seconds: float,
               clock=time.perf_counter) -> Window:
    reqs = plan.window
    grace = mix.get("grace_s", 60.0)
    floor = mix.get("backlog", {}).get("min_queued_sessions", 0)
    by_session: Dict[str, Req] = {}
    by_query: Dict[int, Req] = {}
    lms: List[Req] = []
    out = Window(requests=list(reqs))
    acks_seen = len(memory.acks)
    answers_seen = len(memory.answers)
    i = 0
    t0 = out.t0 = clock()

    def submit(r: Req, now: float) -> None:
        r.submitted = now
        out.lateness.append(now - r.due)
        if r.kind in ("write", "session"):
            by_session[r.payload.session_id] = r
            engine.submit_session(r.payload)
        elif r.kind == "query":
            by_query[id(r.payload)] = r
            r.rid = engine.submit_query(r.payload)
        else:
            toks, max_new = r.payload
            r.rid = engine.submit(toks, max_new_tokens=max_new)
            r.obj = engine.queue[-1]
            lms.append(r)

    def harvest(t_end: float) -> None:
        nonlocal acks_seen, answers_seen
        for t, sessions in memory.acks[acks_seen:]:
            for s in sessions:
                r = by_session.pop(s.session_id, None)
                if r is not None:
                    r.done = t - t0
                    if r.kind == "session":
                        out.sessions.append(s)
        acks_seen = len(memory.acks)
        for t, ids in memory.answers[answers_seen:]:
            for q in ids:
                r = by_query.pop(q, None)
                if r is not None:
                    r.done = t - t0
                    engine.pop_query_result(r.rid)
        answers_seen = len(memory.answers)
        lengths = []
        for r in list(lms):
            n = len(r.obj.out_tokens)
            if n > len(r.tokens):
                if not r.tokens:
                    out.prefill_lengths.append(len(r.payload[0]))
                lengths.append(len(r.payload[0]) + n)
                r.tokens.extend([t_end] * (n - len(r.tokens)))
            if r.obj.finished_s:
                r.done = t_end
                lms.remove(r)
        out.decode_lengths.append(lengths)
        engine.finished.clear()

    while True:
        now = clock() - t0
        while i < len(reqs) and reqs[i].due <= now:   # every due is < seconds
            submit(reqs[i], now)
            i += 1
        if now < seconds:
            if plan.backlog is not None:
                while len(engine.ingest_queue) < floor:
                    submit(Req("session", now, next(plan.backlog)), now)
        elif plan.backlog is not None or not (by_session or by_query or lms) \
                or now >= seconds + grace:
            break
        if busy(engine):
            engine.step()
            t_end = clock() - t0
            out.step_ends.append(t_end)
            harvest(t_end)
        elif now < seconds:
            nxt = reqs[i].due if i < len(reqs) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - now))
        else:
            break
    out.close = out.step_ends[-1] if plan.backlog is not None else clock() - t0
    return out
