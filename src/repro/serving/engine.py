"""Batched serving engine: continuous batching + shared-prefix KV reuse.

This is the layer MemForest's write path runs on in production: chunk
extraction calls share a long prompt prefix (the extraction instruction), so
the engine computes that prefix KV ONCE per batch shape and broadcasts it
across slots — the paper's §5.2 note that "much of this overhead is repeated
prompt prefixes and can be amortized by prefix caching", realized.

Continuous batching: fixed slot array; finished sequences are evicted and
queued requests admitted between decode steps, so occupancy stays high under
ragged output lengths.

Ingest lane: when the engine is built with a memory system, whole-session
write requests queue alongside decode traffic and drain between decode steps
as ONE ``ingest_batch`` call per engine iteration — write traffic rides the
same continuous-batching loop, so concurrent tenants' sessions share encoder
forwards and tree_refresh launches (core/ingest.py).

Query lane: the read-path mirror of the ingest lane. Retrieval requests
queued via ``submit_query`` drain between decode steps as ONE
``query_batch`` call per engine iteration, so concurrent tenants' queries
share the encoder forward, the fused topk_sim index scans, and the
level-synchronous browse launches (core/retrieval.py). Decode, ingest, and
query traffic all ride the same continuous-batching loop.

Multi-device serve: pass ``sharded=ShardedServeConfig(devices=N)`` to shard
the memory system's serve path over a 1-D data mesh
(``launch.mesh.make_data_mesh`` + ``MemForestSystem.set_mesh``): fact-index
rows round-robin across devices with shard-local top-k + candidate merge,
browse lanes and flush refresh batches data-parallel, roots replicated.
Results are exactly identical to single-device serve (kernels/shard_ops.py).
A one-device mesh is the mesh=None fast path; asking for more devices than
are present raises.

Maintenance lane: when built with a ``maintenance`` plane
(core/maintenance_plane.py), ingest drains stop flushing inline
(``defer_flush=True``) and the engine instead runs a bounded slice of
maintenance work — summary refresh, compaction, queued merges — per step.
Flushes no longer block the ingest or query drains; they interleave with
the decode cadence (or run on the plane's background thread).

Residency lane: pass ``residency=ResidencyManager(...)``
(core/residency.py) and ``submit_session``/``submit_query`` accept a
``tenant=`` id routed through the hot/cold tier — cold tenants rehydrate
transparently inside the drains (queries may answer from the always-
resident digest instead), and budget enforcement (demotion = snapshot +
device-cache free) runs as its own bounded drain after the maintenance
lane, so eviction work never sits on a decode step. ``tenant=None``
requests keep using the engine's single ``memory`` system unchanged.

Observability: pass ``obs=Observability(...)`` (repro/obs) — or rely on the
per-engine default — and every step phase (admit, prefill, decode, the
ingest/query/maintenance/residency drains) runs under a span; the legacy
counter set lives in the ``serve/*`` registry namespace and per-request
queue-to-done waits stream into ``serve/{ingest,query}_wait_s`` histograms.
Tracing is off by default (span sites cost one boolean check);
``repro.obs.enable_tracing(sink)`` lights up the whole process.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoder import bucket
from repro.data.tokenizer import HashTokenizer
from repro.models.factory import Model
from repro.obs import Observability, get_obs


@dataclass
class Request:
    req_id: int
    prompt_tokens: List[int]
    max_new_tokens: int = 8
    prefix_key: Optional[str] = None    # shared-prefix cache key
    out_tokens: List[int] = field(default_factory=list)
    submitted_s: float = 0.0
    finished_s: float = 0.0


class PrefixCache:
    """Prefill reuse cache for shared prompt prefixes.

    Granularity: one entry per (prefix_key, padded admission signature) —
    the prefill of a whole padded token block. Prefill is a pure function
    of the padded token matrix and its prompt lengths, so when an admission
    with the same prefix_key reproduces the same block (the common serving
    pattern: repeated instruction-prefix prompts landing in freed slots),
    the cached (logits, KV) are reused and the prefill launch is skipped
    entirely.
    Finer prefix-segment reuse (prefix KV + suffix-only prefill) needs a
    position-offset prefill in the model API — ROADMAP open item.

    Each entry pins a full-width prefill (logits + KV tree) on device, so
    ``max_entries`` bounds the pinned footprint at max_entries x one engine
    cache; eviction is FIFO."""

    def __init__(self, max_entries: int = 4):
        self.entries: Dict[Tuple, Tuple] = {}
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0

    def get(self, key: str, sig: Tuple):
        e = self.entries.get((key, sig))
        if e is not None:
            self.hits += 1
            return e
        self.misses += 1
        return None

    def put(self, key: str, sig: Tuple, logits, cache) -> None:
        if len(self.entries) >= self.max_entries:
            self.entries.pop(next(iter(self.entries)))
        self.entries[(key, sig)] = (logits, cache)


@dataclass(frozen=True)
class ShardedServeConfig:
    """Multi-device serve knobs. ``devices=0`` means all local devices; one
    device is the single-device path, more than are present raises."""
    devices: int = 0
    axis: str = "data"


class ServeEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_len: int = 512, eos_id: int = 2,
                 memory=None, max_ingest_batch: int = 16,
                 max_query_batch: int = 32,
                 maintenance=None, maintenance_budget: int = 1,
                 sharded: Optional[ShardedServeConfig] = None,
                 residency=None, residency_budget: int = 1,
                 obs: Optional[Observability] = None):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * max_batch
        self.finished: List[Request] = []
        self.cache = None
        self.prefix_cache = PrefixCache()
        self._next_id = 0
        # observability: every legacy counter below now lives in the
        # registry (serve/* namespace) and is read back through a property,
        # so engine.metrics() reports through the registry while attribute
        # access (engine.ingest_sessions, ...) keeps working. Span sites
        # (engine.step phases) go through self.obs.span and cost one bool
        # check while tracing is disabled.
        self.obs = get_obs(obs)
        reg = self.obs.registry
        self._m_steps = reg.counter("serve/decode_steps")
        self._m_decoded = reg.counter("serve/decoded_tokens")
        self._m_occupancy = reg.counter("serve/occupancy_sum")
        self._m_prefills = reg.counter("serve/prefills")
        self._m_prefills_reused = reg.counter("serve/prefills_reused")
        self._m_ingest_batches = reg.counter("serve/ingest_batches")
        self._m_ingest_sessions = reg.counter("serve/ingest_sessions")
        self._m_query_batches = reg.counter("serve/query_batches")
        self._m_queries_served = reg.counter("serve/queries_served")
        self._m_maintenance_turns = reg.counter("serve/maintenance_turns")
        self._m_residency_turns = reg.counter("serve/residency_turns")
        # per-request queue-to-done latency distributions (always on —
        # these are metrics, not traces; a record is ~100ns)
        self._h_ingest_wait = reg.histogram("serve/ingest_wait_s")
        self._h_query_wait = reg.histogram("serve/query_wait_s")
        self._h_decode_request = reg.histogram("serve/decode_request_s")
        # ingest-request lane: write traffic (whole sessions bound for the
        # memory substrate) rides the same engine loop as decode slots —
        # everything queued between two engine steps drains as ONE
        # MemForestSystem.ingest_batch call (cross-tenant write batching)
        self.memory = memory
        # multi-device serve: attach a data mesh to the memory system so the
        # ingest/query drains below run the sharded serve path transparently
        self.serve_mesh = None
        if sharded is not None and memory is not None:
            from repro.launch.mesh import make_data_mesh

            self.serve_mesh = make_data_mesh(sharded.devices, sharded.axis)
            memory.set_mesh(self.serve_mesh, sharded.axis)
        self.max_ingest_batch = max_ingest_batch
        self.ingest_queue: List = []
        # query-request lane: read traffic mirrors the ingest lane —
        # everything queued between two engine steps drains as ONE
        # MemForestSystem.query_batch call (cross-tenant read batching)
        self.max_query_batch = max_query_batch
        self.query_queue: List = []
        self.query_results: Dict[int, object] = {}
        # maintenance lane: with a plane attached, ingest drains defer their
        # flush and the engine drains `maintenance_budget` units of refresh/
        # compaction/merge work per step instead. The plane's lock guards
        # forest access when its background thread is running.
        self.maintenance = maintenance
        self.maintenance_budget = maintenance_budget
        # residency lane: multi-tenant hot/cold tier. The engine owns budget
        # enforcement (auto_enforce off): demotions drain at most
        # ``residency_budget`` per step AFTER the serve lanes, so eviction
        # (snapshot + device free) never blocks a decode step.
        self.residency = residency
        self.residency_budget = residency_budget
        if residency is not None:
            residency.auto_enforce = False

        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len)
        )
        # The live cache is donated to the step that replaces it, so a
        # full-width KV cache is never held twice on device (at phi3-mini
        # width, B=8 x 1024 tokens, one cache is 3.2 GB).
        self._decode = jax.jit(model.decode, donate_argnums=(2,))
        # The admission merge is a masked select, not a gather + scatter:
        # with the old cache donated it writes in place and needs no
        # slot-sized temporaries, and one compile serves any slot count.
        # Each cache leaf's lane axis is the one whose size follows the
        # batch in the model's own cache shapes (a stack of any depth, or
        # several stacks, merges alike); a leaf without one is kept.
        def merge(old_cache, new_cache, take):
            one, two = (jax.tree.leaves(model.cache_specs(b, max_len)) for b in (1, 2))
            lane_axes = [next((i for i, (a, b) in enumerate(zip(s1.shape, s2.shape))
                               if a != b), None) for s1, s2 in zip(one, two)]
            olds, tree = jax.tree.flatten(old_cache)
            out = []
            for ax, old, new in zip(lane_axes, olds, jax.tree.leaves(new_cache)):
                if ax is not None:
                    shape = [1] * old.ndim
                    shape[ax] = max_batch
                    old = jnp.where(take.reshape(shape), new, old)
                out.append(old)
            return jax.tree.unflatten(tree, out)

        self._merge = jax.jit(merge, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # registry-backed legacy counters (attribute back-compat)
    # ------------------------------------------------------------------
    @property
    def steps(self) -> int:
        return self._m_steps.value

    @property
    def decoded_tokens(self) -> int:
        return self._m_decoded.value

    @property
    def occupancy_sum(self) -> float:
        return self._m_occupancy.value

    @property
    def prefills(self) -> int:
        return self._m_prefills.value

    @property
    def prefills_reused(self) -> int:
        return self._m_prefills_reused.value

    @property
    def ingest_batches(self) -> int:
        return self._m_ingest_batches.value

    @property
    def ingest_sessions(self) -> int:
        return self._m_ingest_sessions.value

    @property
    def query_batches(self) -> int:
        return self._m_query_batches.value

    @property
    def queries_served(self) -> int:
        return self._m_queries_served.value

    @property
    def maintenance_turns(self) -> int:
        return self._m_maintenance_turns.value

    @property
    def residency_turns(self) -> int:
        return self._m_residency_turns.value

    # ------------------------------------------------------------------
    def submit(self, prompt_tokens: List[int], max_new_tokens: int = 8,
               prefix_key: Optional[str] = None) -> int:
        """Queue a decode request. Its prompt and the tokens it may decode
        must fit the cache: a request that would run past ``max_len``
        raises here instead of overwriting its own cache's last slot."""
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if len(prompt_tokens) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt of {len(prompt_tokens)} tokens + max_new_tokens="
                f"{max_new_tokens} exceeds max_len={self.max_len}")
        r = Request(self._next_id, list(prompt_tokens), max_new_tokens,
                    prefix_key, submitted_s=time.perf_counter())
        self._next_id += 1
        self.queue.append(r)
        return r.req_id

    def submit_session(self, session, *, tenant: Optional[str] = None) -> None:
        """Queue a session for the ingest lane. ``tenant`` routes the write
        through the residency tier (rehydrating a cold tenant on drain);
        None targets the engine's single memory system."""
        if tenant is not None:
            if self.residency is None:
                raise RuntimeError(
                    "tenant= requires a ResidencyManager (residency=)")
        elif self.memory is None:
            raise RuntimeError("ServeEngine was built without a memory system")
        self.ingest_queue.append((tenant, session, time.perf_counter()))

    def _memory_lock(self):
        """Forest-access guard: the maintenance plane's lock when one is
        attached (its background worker may be mutating derived state), a
        no-op otherwise."""
        if self.maintenance is not None:
            return self.maintenance.lock
        return contextlib.nullcontext()

    def _drain_ingest(self) -> int:
        """One ingest-lane turn: everything queued (capped) goes through a
        single batched write per destination — the shared memory system, or
        one ``ResidencyManager.ingest`` per tenant (cold tenants rehydrate
        here, inside the drain, not on the submit path). With a maintenance
        plane attached the shared-system flush is deferred to the plane.
        Returns sessions ingested."""
        if not self.ingest_queue:
            return 0
        batch = self.ingest_queue[: self.max_ingest_batch]
        del self.ingest_queue[: len(batch)]
        with self.obs.span("engine.drain.ingest", sessions=len(batch)):
            groups: Dict[Optional[str], List] = {}
            for tenant, session, _t in batch:
                groups.setdefault(tenant, []).append(session)
            for tenant, sessions in groups.items():
                if tenant is not None:
                    self.residency.ingest(tenant, sessions)
                    self._m_ingest_batches.inc()
                    continue
                with self._memory_lock():
                    if self.maintenance is not None:
                        self.memory.ingest_batch(sessions, defer_flush=True)
                    else:
                        self.memory.ingest_batch(sessions)
                self._m_ingest_batches.inc()
        now = time.perf_counter()
        for _tenant, _session, t in batch:
            self._h_ingest_wait.record(now - t)
        self._m_ingest_sessions.inc(len(batch))
        return len(batch)

    def submit_query(self, query, *, mode: Optional[str] = None,
                     final_topk: Optional[int] = None,
                     tenant: Optional[str] = None) -> int:
        """Queue a retrieval request for the query lane. ``tenant`` routes
        through the residency tier (digest answer or rehydrate on drain);
        None targets the engine's single memory system. The result lands in
        ``query_results[req_id]`` after the engine step that drains it."""
        if tenant is not None:
            if self.residency is None:
                raise RuntimeError(
                    "tenant= requires a ResidencyManager (residency=)")
        elif self.memory is None:
            raise RuntimeError("ServeEngine was built without a memory system")
        rid = self._next_id
        self._next_id += 1
        self.query_queue.append((rid, tenant, query, mode, final_topk,
                                 time.perf_counter()))
        return rid

    def pop_query_result(self, req_id: int):
        """Consume a finished query's result (None if not served yet).
        Long-lived deployments must consume results — ``query_results``
        holds everything unconsumed, like ``finished`` does for decodes."""
        return self.query_results.pop(req_id, None)

    def _drain_queries(self) -> int:
        """One query-lane turn: everything queued (capped) goes through
        batched retrieval — one ``query_batch`` per distinct (tenant, mode,
        topk) group, usually exactly one. Tenant groups run through the
        residency tier (digest gate / rehydration happen here, inside the
        drain). Returns queries answered."""
        if not self.query_queue:
            return 0
        batch = self.query_queue[: self.max_query_batch]
        del self.query_queue[: len(batch)]
        with self.obs.span("engine.drain.query", queries=len(batch)):
            groups: Dict[Tuple, List] = {}
            for rid, tenant, q, mode, topk, _t in batch:
                groups.setdefault((tenant, mode, topk), []).append((rid, q))
            for (tenant, mode, topk), items in groups.items():
                if tenant is not None:
                    res = self.residency.query_batch(
                        tenant, [q for _, q in items], mode=mode,
                        final_topk=topk)
                else:
                    with self._memory_lock():
                        res = self.memory.query_batch(
                            [q for _, q in items], mode=mode, final_topk=topk)
                for (rid, _q), r in zip(items, res):
                    self.query_results[rid] = r
                self._m_query_batches.inc()
        now = time.perf_counter()
        for rec in batch:
            self._h_query_wait.record(now - rec[5])
        self._m_queries_served.inc(len(batch))
        return len(batch)

    # ------------------------------------------------------------------
    def _admit(self) -> List[Request]:
        """Fill free slots from the queue. New slots are prefilled as a
        full-width batch (static shapes) and their cache rows SCATTERED into
        the live cache — active decodes are untouched (continuous batching).
        """
        free = [i for i, a in enumerate(self.active) if a is None]
        if not free or not self.queue:
            return []
        admitted_slots: List[int] = []
        for i in free:
            if not self.queue:
                break
            self.active[i] = self.queue.pop(0)
            admitted_slots.append(i)

        B = self.max_batch
        prompts = [
            (self.active[i].prompt_tokens if self.active[i] is not None and i in admitted_slots
             else [0])
            for i in range(B)
        ]
        # Attention-only trunks prefill prompts left-aligned at a bucketed
        # width with their true lengths: the padding sits after each prompt,
        # where causal attention never lets the prompt see it and decode
        # writes over it, so a padded prefill decodes the same tokens as an
        # exact-width one and leaves every slot past the prompt to decoding.
        # A power-of-two width (16 up to max_len) bounds the set of prefill
        # compiles and lets the flash kernel's block divide it. Recurrent
        # trunks would carry that padding into their state, so they prefill
        # right-aligned at the exact longest-prompt width.
        padded = self.model.cfg.family in ("dense", "moe", "vlm")
        longest = max(len(p) for p in prompts)
        L = bucket(longest, 16, self.max_len) if padded else max(longest, 2)
        lengths = np.ones(B, np.int32)
        toks = np.zeros((B, L), np.int32)
        for i in admitted_slots:
            p = prompts[i]
            lengths[i] = len(p)
            if padded:
                toks[i, :len(p)] = p          # left-align, pad after
            else:
                toks[i, L - len(p):] = p      # right-align
        batch = {"tokens": jnp.asarray(toks)}
        if padded:
            batch["lengths"] = jnp.asarray(lengths)
        # prefill reuse: when every admitted request carries the same
        # prefix_key and this admission reproduces a cached padded token
        # block, the prefill launch is skipped (prefill is a pure function
        # of the block and its lengths). jax arrays are immutable and the
        # cache merge below is functional, so reuse is aliasing-safe.
        pkeys = {self.active[i].prefix_key for i in admitted_slots}
        pkey = pkeys.pop() if len(pkeys) == 1 else None
        sig = ((tuple(admitted_slots), toks.tobytes(), lengths.tobytes())
               if pkey is not None else None)
        hit = self.prefix_cache.get(pkey, sig) if pkey is not None else None
        self._m_prefills.inc()
        if hit is not None:
            logits, new_cache = hit
            self._m_prefills_reused.inc()
        else:
            with self.obs.span("engine.prefill", slots=len(admitted_slots),
                               width=int(L)):
                logits, new_cache = self._prefill(self.params, batch)
            if pkey is not None:
                self.prefix_cache.put(pkey, sig, logits, new_cache)

        if self.cache is None:
            # decode donates the live cache: never hand it a prefix-cache
            # entry's buffers, which later hits must still be able to read
            self.cache = (new_cache if pkey is None
                          else jax.tree.map(jnp.copy, new_cache))
            self._last_logits = logits
        else:
            take = np.zeros(B, bool)
            take[admitted_slots] = True
            take = jnp.asarray(take)
            self.cache = self._merge(self.cache, new_cache, take)
            self._last_logits = jnp.where(take[:, None], logits,
                                          self._last_logits)
        return [self.active[i] for i in admitted_slots]

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: admit + one decode step for all active,
        then one ingest-lane and one query-lane drain. Returns number of
        finished decode requests. Every phase (admit incl. prefill, decode,
        the four drains) runs under its own span, so enabling tracing yields
        a per-phase latency distribution (``span/engine.*`` histograms)."""
        with self.obs.span("engine.step"):
            with self.obs.span("engine.admit"):
                self._admit()
            act = [a for a in self.active if a is not None]
            if not act:
                self._drain_ingest()
                self._drain_queries()
                self._drain_maintenance()
                self._drain_residency()
                return 0
            self._m_occupancy.inc(len(act) / self.max_batch)
            self._m_steps.inc()

            with self.obs.span("engine.decode", lanes=len(act)):
                # greedy next token from last logits
                # the one sanctioned sync: greedy sampling must read the
                # token ids before Python can append them to lane buffers
                # memlint: ignore[host-sync]
                next_tok = np.asarray(jnp.argmax(self._last_logits, axis=-1))
                for i, a in enumerate(self.active):
                    if a is None:
                        continue
                    a.out_tokens.append(int(next_tok[i]))
                    self._m_decoded.inc()
                batch = {"tokens": jnp.asarray(next_tok.astype(np.int32))}
                self._last_logits, self.cache = self._decode(
                    self.params, batch, self.cache)

            finished = 0
            for i, a in enumerate(self.active):
                if a is None:
                    continue
                if len(a.out_tokens) >= a.max_new_tokens or a.out_tokens[-1] == self.eos_id:
                    a.finished_s = time.perf_counter()
                    self._h_decode_request.record(a.finished_s - a.submitted_s)
                    self.finished.append(a)
                    self.active[i] = None
                    finished += 1
            self._drain_ingest()
            self._drain_queries()
            self._drain_maintenance()
            self._drain_residency()
            return finished

    def _drain_maintenance(self) -> int:
        """One maintenance-lane turn: a bounded slice of refresh/compaction/
        merge work (no-op when the plane runs its own background thread with
        budget 0, or when no plane is attached)."""
        if self.maintenance is None or self.maintenance_budget <= 0:
            return 0
        if self.maintenance.pending() == 0:
            return 0
        with self.obs.span("engine.drain.maintenance"):
            done = self.maintenance.run_some(self.maintenance_budget)["units"]
        if done:
            self._m_maintenance_turns.inc()
        return done

    def _drain_residency(self) -> int:
        """One residency-lane turn: demote at most ``residency_budget``
        over-budget tenants (snapshot + device-cache free). Bounded per
        step, so eviction interleaves with the decode cadence instead of
        blocking it — the residency twin of the maintenance drain."""
        if self.residency is None or self.residency_budget <= 0:
            return 0
        if self.residency.over_budget() == 0:
            return 0
        with self.obs.span("engine.drain.residency"):
            done = self.residency.enforce_budget(self.residency_budget)
        if done:
            self._m_residency_turns.inc()
        return done

    # ------------------------------------------------------------------
    def run_until_drained(self, max_steps: int = 10000) -> List[Request]:
        for _ in range(max_steps):
            if not self.queue and not self.ingest_queue \
                    and not self.query_queue \
                    and all(a is None for a in self.active):
                # cooperative maintenance keeps stepping until its backlog
                # (deferred flushes, compactions, merges) is drained too,
                # and residency until the hot set is back within budget
                if (self.maintenance is None or self.maintenance_budget <= 0
                        or self.maintenance.pending() == 0) \
                        and (self.residency is None
                             or self.residency_budget <= 0
                             or self.residency.over_budget() == 0):
                    break
            self.step()
        return self.finished

    def metrics(self) -> Dict[str, float]:
        """Legacy flat metrics dict, now REPORTED THROUGH the registry: every
        counter below is a ``serve/*`` registry counter (the properties read
        them back), so ``engine.obs.registry.snapshot()`` and this dict can
        never disagree (tests/test_obs.py metric-coherence test)."""
        steps = self._m_steps.value
        return {
            "decode_steps": steps,
            "decoded_tokens": self._m_decoded.value,
            "mean_occupancy": self._m_occupancy.value / max(steps, 1),
            "prefix_hits": self.prefix_cache.hits,
            "prefix_misses": self.prefix_cache.misses,
            "prefills": self._m_prefills.value,
            "prefills_reused": self._m_prefills_reused.value,
            "ingest_batches": self._m_ingest_batches.value,
            "ingest_sessions": self._m_ingest_sessions.value,
            "mean_ingest_batch": self._m_ingest_sessions.value
            / max(self._m_ingest_batches.value, 1),
            "query_batches": self._m_query_batches.value,
            "queries_served": self._m_queries_served.value,
            "mean_query_batch": self._m_queries_served.value
            / max(self._m_query_batches.value, 1),
            "maintenance_turns": self._m_maintenance_turns.value,
            "residency_turns": self._m_residency_turns.value,
            "serve_devices": (self.serve_mesh.devices.size
                              if self.serve_mesh is not None else 1),
            # per-request wait distributions (additive keys, seconds)
            "ingest_wait_p50_s": self._h_ingest_wait.quantile(0.5),
            "ingest_wait_p99_s": self._h_ingest_wait.quantile(0.99),
            "query_wait_p50_s": self._h_query_wait.quantile(0.5),
            "query_wait_p99_s": self._h_query_wait.quantile(0.99),
            **(self.maintenance.metrics() if self.maintenance is not None else {}),
            # hot_tenants / evictions / rehydrations / digest_answers /
            # device_bytes(_est) ride straight into the engine metrics dict
            **(self.residency.metrics() if self.residency is not None else {}),
        }

    def latency_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase span-duration summaries (populated while tracing is
        enabled): {span name: {count, mean_s, p50_s, p90_s, p99_s, ...}}."""
        return self.obs.registry.latency_summary()


class BatchedEncoderServer:
    """The extraction front-end: batches chunk-encode requests from many
    concurrent sessions into single forwards (the write-path parallelism),
    with shared-prefix accounting."""

    def __init__(self, encoder, shared_prefix: str = "[extract facts] "):
        self.encoder = encoder
        self.shared_prefix = shared_prefix
        self.prefix_tokens_saved = 0

    def encode_chunks(self, chunk_texts: List[str]) -> np.ndarray:
        # prefix is shared: tokens for it are paid once per batch, not per chunk
        n = len(chunk_texts)
        if n == 0:
            return np.zeros((0, self.encoder.dim), np.float32)
        prefix_tok = max(len(self.shared_prefix.split()), 1)
        self.prefix_tokens_saved += prefix_tok * (n - 1)
        return self.encoder.encode([self.shared_prefix + t for t in chunk_texts])
