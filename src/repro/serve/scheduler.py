"""Slot-based continuous batching over the paged quantized KV cache.

Replaces the fixed-bucket ``ServeEngine.generate`` loop for mixed-length
streams (DESIGN.md §17).  One decode step advances EVERY active slot; a
slot frees the moment its request completes, so the next waiting request
admits mid-stream instead of waiting for the whole bucket to drain.

Admission policy (§17):

  * submit-time validation: a request that could never fit the pool
    (``ceil((P + max_new) / page_size)`` pages beyond the per-seq cap or
    the whole pool) is rejected with ``ConfigError`` up front;
  * admit = reserve a slot and the prompt's pages, prefill the prompt
    through the DENSE 16-bit path (batch 1, ``max_len == P``), quantize
    the rows into the reserved pages (``commit_prefill_to_paged``), and
    sample the first token from the prefill logits;
  * lazy extension: pages are allocated one page-boundary at a time as a
    sequence grows; when the pool is dry the YOUNGEST request is
    preempted (LIFO) — released entirely and pushed back to the *front*
    of the waiting queue, so the oldest work is never starved;
  * restart-safe sampling: the stream for generated-token ``g`` of
    request ``rid`` is ``fold_in(fold_in(PRNGKey(seed), rid), g)`` —
    independent of scheduling, so a preempted request regenerates the
    same tokens it lost and differential tests stay exact.

Throughput note: sampling happens ON DEVICE inside the jitted step (the
scheduler only needs token COUNTS, which it knows, to admit/evict/
complete — never token values), so decode steps queue back-to-back with
no per-step host round-trip; the host blocks once per completion (the
latency observation) and copies the token matrix once per ``serve``.

Observability (DESIGN.md §14): with phase tracing on, ``serve`` opens
host spans on the profiler's clock — ``tel.serve`` around the call;
``tel.serve.admit`` (``rid``) with its ``.prefill`` and ``.commit``
dispatches; ``tel.serve.extend`` (page extension, eviction);
``tel.serve.upload``; ``tel.serve.burst`` (``k``); ``tel.serve.complete``
(``rid``) with its ``.sync``; ``tel.serve.collect``.  With a registry,
each burst of ``k`` decode steps counts, on the host and with no sync,
``serve/sched/{bursts, decode_steps, slot_steps}`` (live slots × k) and
``serve/kv/pages_live`` (their allocated pages × k) against
``serve/kv/pages_gathered`` (the whole table × k: what the gather reads).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.errors import ConfigError
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model as M
from repro.serve import engine as engine_lib
from repro.serve.kvcache import PagedKVCache, PagedKVConfig, kv_bytes_per_token
from repro.telemetry.tracing import span


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt: tuple                  # token ids
    max_new_tokens: int


@dataclasses.dataclass
class SchedulerConfig:
    kv: PagedKVConfig = dataclasses.field(default_factory=PagedKVConfig)
    temperature: float = 0.0       # 0 => greedy
    seed: int = 0
    # gather-dequant kernel (pallas|interpret|jnp); the platform's own
    # path by default, so a TPU runs the Pallas kernel
    impl: str = dataclasses.field(default_factory=lambda: ops.default_impl())


class ContinuousBatchingEngine:
    """Continuous batching: admit/evict per decode step, paged 8/4-bit KV."""

    def __init__(self, cfg, params, sched_cfg: Optional[SchedulerConfig] =
                 None, registry=None):
        self.cfg = cfg
        self.params = params
        self.scfg = sched_cfg or SchedulerConfig()
        self.registry = registry
        self.kv = PagedKVCache(self.scfg.kv)
        kvc = self.scfg.kv
        self.caches = M.init_paged_cache(cfg, kvc.n_slots, kvc.n_pages,
                                         kvc.page_size, kvc.kv_bits)
        self._lat_counts = np.zeros((engine_lib.N_LATENCY_BINS,), np.int64)
        self._latencies_ms: list = []
        self._last_tok = jnp.zeros((kvc.n_slots,), jnp.int32)
        self._live: dict = {}      # rid -> live-request record (see _admit)
        base_key = jax.random.PRNGKey(self.scfg.seed)
        temp = self.scfg.temperature

        def _sample_rows(rows, rids, gen_idx):
            """rows: (B, V) logits -> (B,) sampled tokens, on device."""
            if temp <= 0.0:
                return jnp.argmax(rows, axis=-1).astype(jnp.int32)

            def one(row, rid, g):
                key = jax.random.fold_in(jax.random.fold_in(base_key, rid),
                                         g)
                return jax.random.categorical(key, row / temp)

            return jax.vmap(one)(rows, rids, gen_idx).astype(jnp.int32)

        impl = self.scfg.impl

        def _step(params, last_tok, caches, table, pos, rids, gen_idx):
            """One decode step, all bookkeeping on device: sample in-jit,
            advance positions/gen counters in-jit — between scheduling
            events (admit/complete/evict/page-boundary) the host launches
            these back-to-back with zero uploads or syncs."""
            paged = L.PagedContext(table, pos, impl=impl)
            logits, caches = M.paged_decode_step(cfg, params,
                                                 last_tok[:, None], caches,
                                                 paged)
            tok = _sample_rows(logits[:, 0], rids, gen_idx)
            active = pos >= 0
            return (tok, caches, jnp.where(active, pos + 1, pos),
                    jnp.where(active, gen_idx + 1, gen_idx))

        def _sample_one(row, rid, g):
            return _sample_rows(row[None], jnp.asarray([rid]),
                                jnp.asarray([g]))[0]

        # pages update in place: the cache pytree is donated (§17 contract)
        self._decode = jax.jit(_step, donate_argnums=(2,))
        self._sample1 = jax.jit(_sample_one)
        self._prefills: dict = {}  # prompt_len -> jitted dense prefill
        self._commits: dict = {}   # prompt_len -> jitted page commit

    # ----------------------------------------------------------- helpers
    def _prefill_fn(self, P: int):
        if P not in self._prefills:
            cfg16 = dataclasses.replace(self.cfg, kv_cache_bits=16)

            def _pf(params, tokens):
                return M.prefill(cfg16, params, tokens, max_len=P)

            self._prefills[P] = jax.jit(_pf)
        return self._prefills[P]

    def _commit_fn(self, P: int):
        if P not in self._commits:
            kv_bits = self.scfg.kv.kv_bits

            def _cm(paged_caches, dense, slot, table_row):
                return M.commit_prefill_to_paged(self.cfg, paged_caches,
                                                 dense, slot, table_row, P,
                                                 kv_bits=kv_bits)

            self._commits[P] = jax.jit(_cm, donate_argnums=(0,))
        return self._commits[P]

    def _count(self, name: str, n: int = 1):
        if self.registry is not None:
            self.registry.counter(name).inc(n)

    def _gauges(self):
        if self.registry is None:
            return
        kvc = self.scfg.kv
        self.registry.gauge("serve/sched/slot_occupancy").set(
            self.kv.n_active / kvc.n_slots)
        self.registry.gauge("serve/sched/page_occupancy").set(
            self.kv.alloc.occupancy)

    def _observe_request(self, wall_ms: float):
        self._latencies_ms.append(wall_ms)
        if self.registry is None:
            return
        self._lat_counts[bisect.bisect(engine_lib.LATENCY_BIN_EDGES_MS,
                                       wall_ms)] += 1
        self.registry.histogram(
            "serve/latency_ms",
            n_bins=engine_lib.N_LATENCY_BINS).observe_counts(self._lat_counts)

    # ------------------------------------------------------- transitions
    def _validate(self, req: Request):
        kvc = self.scfg.kv
        total = len(req.prompt) + req.max_new_tokens
        need = kvc.pages_needed(total)
        if need > kvc.max_pages_per_seq or need > kvc.n_pages:
            raise ConfigError(
                f"request {req.rid}: {total} tokens need {need} pages, "
                f"pool caps at min(max_pages_per_seq={kvc.max_pages_per_seq}"
                f", n_pages={kvc.n_pages})")
        if req.max_new_tokens <= 0:
            raise ConfigError(
                f"request {req.rid}: max_new_tokens must be positive")

    def _admit(self, req: Request) -> bool:
        with span("serve.admit", rid=req.rid):
            P = len(req.prompt)
            slot = self.kv.admit(req.rid, P)
            if slot is None:
                return False
            t0 = time.perf_counter()
            with span("serve.prefill", rid=req.rid):
                logits, dense = self._prefill_fn(P)(
                    self.params,
                    jnp.asarray(np.asarray(req.prompt, np.int32)[None]))
            with span("serve.commit", rid=req.rid):
                self.caches = self._commit_fn(P)(
                    self.caches, dense, slot,
                    jnp.asarray(self.kv.page_table[slot].copy()))
            tok0 = self._sample1(logits[0, -1], req.rid, 0)   # device scalar
            self._last_tok = self._last_tok.at[slot].set(tok0)
            # chain = where each generated token lives, without syncing:
            # ("a", device_scalar) for the admission sample, ("s", step_idx)
            # for decode steps (the slot row of that step's token vector)
            self._live[req.rid] = {"req": req, "t0": t0, "n_out": 1,
                                   "chain": [("a", tok0)]}
            self._count("serve/sched/admitted")
            self._count("serve/prompt_tokens", P)
            return True

    def _evict_youngest(self, waiting, protect=None) -> bool:
        """Preempt the youngest admitted request back to the queue front."""
        victims = sorted(self.kv.slots.values(), key=lambda s: -s.admit_order)
        for st in victims:
            if st.rid == protect:
                continue
            self.kv.release(st.rid)
            waiting.appendleft(self._live.pop(st.rid)["req"])
            self._count("serve/sched/evictions")
            return True
        return False

    def _complete(self, rid: int, done: dict):
        with span("serve.complete", rid=rid):
            st = self._live.pop(rid)
            self.kv.release(rid)
            # block on the request's last token: the one per-request device
            # sync, and what makes the latency observation wall-clock-honest
            last = st["chain"][-1]
            with span("serve.sync", rid=rid):
                jax.block_until_ready(last[1] if last[0] == "a" else
                                      self._last_tok)
            done[rid] = st
            self._observe_request((time.perf_counter() - st["t0"]) * 1e3)
            self._count("serve/sched/completed")
            self._count("serve/generated_tokens", st["n_out"])

    # --------------------------------------------------------------- run
    def serve(self, requests) -> dict:
        """Run every request to completion; returns {rid: (n,) int32}."""
        with span("serve"):
            return self._serve(requests)

    def _serve(self, requests) -> dict:
        for r in requests:
            self._validate(r)
        waiting = collections.deque(requests)
        done: dict = {}
        step_toks: list = []       # per decode step: (B,) device tokens
        step_slots: list = []      # per decode step: {rid: slot} snapshot
        kvc = self.scfg.kv
        t_serve = time.perf_counter()
        while waiting or self._live:
            # 1. admit as many waiting requests as slot+page budget allows
            while waiting and self.kv.free_slot() is not None:
                if not self._admit(waiting[0]):
                    break
                waiting.popleft()
            # 2. single-token completions never reach the decode batch
            for rid in [r for r, st in self._live.items()
                        if st["n_out"] >= st["req"].max_new_tokens]:
                self._complete(rid, done)
            if not self._live:
                # everything completed this turn; retry admission next
                # iteration — unless nothing can fit an EMPTY pool, which
                # validation should have caught
                if waiting and self.kv.alloc.n_allocated == 0 and \
                        not self._admit(waiting[0]):
                    raise ConfigError(
                        f"request {waiting[0].rid} cannot admit into an "
                        f"empty pool — capacity validation is broken")
                if waiting and self.kv.n_active > 0:
                    waiting.popleft()          # the forced admit succeeded
                continue
            # 3. make sure every active slot's write position has a page
            with span("serve.extend"):
                for rid in list(self._live):
                    if rid not in self._live:  # evicted for a prior slot
                        continue
                    while not self.kv.extend(rid):
                        if not self._evict_youngest(waiting, protect=rid):
                            raise ConfigError(
                                f"request {rid} cannot extend with the pool "
                                f"to itself — capacity validation is broken")
            self._gauges()
            # 4. run the next k decode steps back-to-back: scheduling can
            # only change at a completion or a page boundary, both known
            # ahead of time, so until then positions/counters advance on
            # device and the host does no uploads and no syncs
            rids = np.zeros((kvc.n_slots,), np.int32)
            gen = np.zeros((kvc.n_slots,), np.int32)
            snapshot = {}
            k = None
            live_pages = 0
            for rid in self._live:
                slot = self.kv.slot_of(rid)
                st = self._live[rid]
                rids[slot] = rid
                gen[slot] = st["n_out"]
                snapshot[rid] = slot
                pages = len(self.kv.slots[slot].pages)
                live_pages += pages
                to_done = st["req"].max_new_tokens - st["n_out"]
                to_edge = pages * kvc.page_size - self.kv.slots[slot].position
                k = min(x for x in (k, to_done, to_edge) if x is not None)
            # Upload COPIES: on the CPU backend a device array may alias
            # the numpy buffer it was made from, and the host mutates the
            # table and positions while queued decode steps still read them.
            with span("serve.upload"):
                table = jnp.asarray(self.kv.page_table.copy())
                pos = jnp.asarray(self.kv.positions.copy())
                d_rids, d_gen = jnp.asarray(rids), jnp.asarray(gen)
            with span("serve.burst", k=k):
                for _ in range(k):
                    self._last_tok, self.caches, pos, d_gen = self._decode(
                        self.params, self._last_tok, self.caches, table, pos,
                        d_rids, d_gen)
                    step_toks.append(self._last_tok)
                    step_slots.append(snapshot)
            # work of the burst: every slot decodes and the gather reads
            # every slot's whole table, live or not
            self._count("serve/sched/bursts")
            self._count("serve/sched/decode_steps", k)
            self._count("serve/sched/slot_steps", len(snapshot) * k)
            self._count("serve/kv/pages_live", live_pages * k)
            self._count("serve/kv/pages_gathered",
                        kvc.n_slots * kvc.max_pages_per_seq * k)
            # 5. advance host bookkeeping k steps, complete finished
            for rid, slot in snapshot.items():
                st = self._live[rid]
                for j in range(k):
                    self.kv.advance(rid)
                    st["n_out"] += 1
                    st["chain"].append(("s", len(step_toks) - k + j))
                if st["n_out"] >= st["req"].max_new_tokens:
                    self._complete(rid, done)
        with span("serve.collect"):
            # one bulk sync for every decode-step token vector
            mat = np.asarray(jnp.stack(step_toks)) if step_toks else \
                np.zeros((0, kvc.n_slots), np.int32)
            results: dict = {}
            n_gen = 0
            for rid, st in done.items():
                toks = [int(np.asarray(e[1])) if e[0] == "a" else
                        int(mat[e[1], step_slots[e[1]][rid]])
                        for e in st["chain"]]
                results[rid] = np.asarray(toks, np.int32)
                n_gen += len(toks)
        wall = time.perf_counter() - t_serve
        if self.registry is not None and n_gen and wall > 0:
            self.registry.gauge("serve/tokens_per_s").set(n_gen / wall)
            self.registry.gauge("serve/kv_bytes_per_token").set(
                kv_bytes_per_token(self.cfg, kvc.kv_bits))
            self._count("serve/requests", len(results))
        self._gauges()
        return results

    # ----------------------------------------------------------- metrics
    def latency_percentiles(self) -> dict:
        """p50/p99 per-request latency (ms) over everything served."""
        if not self._latencies_ms:
            return {"p50_ms": 0.0, "p99_ms": 0.0}
        arr = np.asarray(self._latencies_ms)
        return {"p50_ms": float(np.percentile(arr, 50)),
                "p99_ms": float(np.percentile(arr, 99))}
