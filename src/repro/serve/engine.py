"""Continuous-batching serving engine.

Scheduler tick:
  1. admit waiting requests while KV pages are available; prefix-cache
     lookups are issued as ONE batched round against the Elim-ABtree index
     (hits share pages — ref-counted);
  2. run one fused decode step for all running requests (static max_batch
     slots; finished slots are masked) via the jitted serve_step;
  3. retire finished requests: their page-table pages are released and
     their prefix blocks (un)published in a second batched round — under
     session churn these rounds are the paper's skewed update-heavy
     workload.

The model step is exactly launch/serve_step; this module is the host-side
control plane (the part of the system vLLM calls the scheduler + block
manager)."""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import backbone, init_params
from repro.models.config import ModelConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import NULL_TRACER
from repro.serve.pages import (
    PAGE,
    PagedKVCache,
    PrefixIndex,
    SessionIndex,
    prefix_hashes,
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_done: Optional[float] = None
    cache_hit_blocks: int = 0


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        *,
        max_batch: int = 8,
        s_max: int = 512,
        n_pages: int = 1024,
        index_mode: str = "elim",
        index_shards: int = 1,
        index_durable_dir: Optional[str] = None,
        index_faults=None,
        pipelined: bool = False,
        group_commit_every: int = 1,
        group_commit_max_wait_s: float = 0.05,
        commit_async: Optional[bool] = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.max_batch = max_batch
        self.s_max = s_max
        # pipelined=True double-buffers the tick: round N's decode is
        # DISPATCHED (JAX async dispatch — no block), round N+1's
        # admit/classify work runs on the host while the device is busy,
        # and only then does the tick fence on the decode result.  The
        # host-work-under-flight fraction is the tick_overlap_frac gauge.
        self.pipelined = pipelined
        # group_commit_every > 1 batches that many index rounds per
        # manifest rename on BOTH journals; commit_async (default: on
        # whenever grouping is on) moves the boundary commit I/O to the
        # durable layer's background thread so no tick pays the fsyncs
        # inline.  run_until_done() drains pending groups at exit.
        if commit_async is None:
            commit_async = group_commit_every > 1
        self.params = init_params(backbone.model_spec(cfg), seed=seed)
        self.kv = PagedKVCache(n_pages)
        # index_shards > 1 partitions both indexes' key spaces into an
        # ABForest (one vmapped round per scheduler tick, per index).
        # Prefix hashes are uniform over the 63-bit domain, so static even
        # splits suffice; session ids are MONOTONE, so the static splits
        # alone would route every live id to one shard — max_keys_per_shard
        # makes the forest re-partition the live id range adaptively (live
        # sessions are bounded by the page pool, so n_pages is the scale).
        # index_durable_dir journals both indexes as DurableForests (one
        # journal lane per shard): a restarted engine pointing at the same
        # directory recovers its prefix cache warm.  index_faults (a
        # FaultPlan / CrashPoint) is installed on both journals; the
        # durable layer's retry + circuit breaker guarantee tick() never
        # raises on a sick disk — it degrades to volatile serving instead
        # (visible via stats()["durability"]).
        self.index = PrefixIndex(
            mode=index_mode,
            shards=index_shards,
            durable_dir=(
                None if index_durable_dir is None
                else os.path.join(index_durable_dir, "prefix")
            ),
            faults=index_faults,
            group_commit_every=group_commit_every,
            group_commit_max_wait_s=group_commit_max_wait_s,
            commit_async=commit_async,
        )
        self.sessions = SessionIndex(
            mode=index_mode,
            shards=index_shards,
            key_space=(0, 1 << 31),
            max_keys_per_shard=(
                None if index_shards == 1 else max(64, n_pages // index_shards)
            ),
            durable_dir=(
                None if index_durable_dir is None
                else os.path.join(index_durable_dir, "sessions")
            ),
            faults=index_faults,
            group_commit_every=group_commit_every,
            group_commit_max_wait_s=group_commit_max_wait_s,
            commit_async=commit_async,
        )
        # engine-level telemetry: tick latency + scheduler counters live in
        # the engine's own registry; the index holders keep theirs (round
        # phases, journal flushes) — stats() stitches both surfaces.
        self.metrics = MetricsRegistry()
        self._tracer = NULL_TRACER
        self._evict_floor = 0  # session ids below this are already swept
        self._retired_since_sweep = 0
        self._max_rid = -1  # highest session id ever admitted
        self.waiting: List[Request] = []
        self.running: Dict[int, Request] = {}
        self.slots: List[Optional[int]] = [None] * max_batch  # slot → rid
        self.pos = np.zeros(max_batch, np.int64)
        self.cache = backbone.init_cache(cfg, max_batch, s_max)
        self.done: List[Request] = []
        self._decode = jax.jit(
            lambda p, c, t, q: backbone.forward_decode(p, c, t, q, cfg)
        )
        # the batch axis of every cache leaf (the layout differs by family)
        one, two = (backbone.cache_spec(cfg, n, s_max) for n in (1, 2))
        batch_axes = jax.tree.map(
            lambda a, b: [x != y for x, y in zip(a.shape, b.shape)].index(True),
            one, two,
        )

        def prefill_step(p, c, t, q, slot):
            """One prompt token of one slot.  The decode step writes every
            slot's cache row at ``q``; keep the other slots' rows, or a
            prefill clobbers the prompts of requests admitted before it."""
            logits, new = backbone.forward_decode(p, c, t, q, cfg)

            def keep_others(n, o, ax):
                mine = jnp.arange(n.shape[ax]) == slot
                shape = [-1 if i == ax else 1 for i in range(n.ndim)]
                return jnp.where(mine.reshape(shape), n, o)

            return logits, jax.tree.map(keep_others, new, c, batch_axes)

        self._prefill_tok = jax.jit(prefill_step)

    # ------------------------------------------------------------------ --

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, t):
        # one tracer for the whole stack: installing it here also times the
        # round-engine phases (and journal commits) under both indexes.
        self._tracer = t
        self.index.tree.tracer = t
        self.sessions.tree.tracer = t

    @property
    def recorder(self):
        """The prefix index's flight recorder (the audit-critical surface:
        publish/lookup rounds).  Assigning installs one recorder on BOTH
        index holders, mirroring the tracer's whole-stack convention."""
        return self.index.tree.recorder

    @recorder.setter
    def recorder(self, r):
        self.index.tree.recorder = r
        self.sessions.tree.recorder = r

    def submit(self, req: Request):
        req.t_submit = time.time()
        self.waiting.append(req)

    def _admit(self):
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            # prefix-cache lookup: one batched round per request admission
            chain = prefix_hashes(req.prompt)
            hits = self.index.lookup_batch([h for h, _ in chain])
            n_hit = 0
            for h in hits:
                if h is None:
                    break
                n_hit += 1
            req.cache_hit_blocks = n_hit
            self.metrics.inc("cache_hit_blocks", n_hit)
            need_pages = max(1, (len(req.prompt) + req.max_new + PAGE - 1) // PAGE)
            pages = self.kv.alloc(req.rid, need_pages)
            if pages is None:
                self.waiting.insert(0, req)
                return
            # publish the prompt's prefix blocks (batched insert round)
            self.index.publish_batch(
                [h for h, _ in chain[n_hit:]], pages[: len(chain) - n_hit] or [0]
            ) if chain[n_hit:] else None
            # session index: rid → first page of the request's page table
            self.sessions.publish_batch([req.rid], [pages[0]])
            self._max_rid = max(self._max_rid, req.rid)
            # teacher-forced prefill through the decode path (simple engine:
            # prompt tokens streamed token-by-token into the slot's cache)
            self.slots[slot] = req.rid
            self.running[req.rid] = req
            self.metrics.inc("admitted")
            self.pos[slot] = 0
            for tok in req.prompt[:-1]:
                self._step_slot(slot, tok)
            req._last_tok = req.prompt[-1]

    def _step_slot(self, slot: int, tok: int):
        tokens = np.zeros(self.max_batch, np.int32)
        tokens[slot] = tok
        logits, self.cache = self._prefill_tok(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.int32(int(self.pos[slot])), jnp.int32(slot),
        )
        self.pos[slot] += 1
        return logits

    def tick(self):
        """One scheduler iteration: admit + fused decode for all running.
        Pipelined mode dispatches the decode first and admits under it."""
        t0 = time.perf_counter()
        tr = self._tracer
        overlap = 0.0
        with tr.span("serve.tick"):
            if self.pipelined:
                overlap = self._tick_pipelined(tr)
            else:
                self._tick_body(tr)
        dt = time.perf_counter() - t0
        self.metrics.inc("ticks")
        self.metrics.observe("tick_latency_s", dt)
        if self.pipelined:
            frac = overlap / dt if dt > 0 else 0.0
            self.metrics.set_gauge("tick_overlap_frac", frac)
            self.metrics.observe("tick_overlap_frac", frac)

    def _tick_body(self, tr):
        with tr.span("serve.admit", waiting=len(self.waiting)):
            self._admit()
        active = [s for s in range(self.max_batch) if self.slots[s] is not None]
        if not active:
            return
        tokens = np.zeros(self.max_batch, np.int32)
        for s in active:
            req = self.running[self.slots[s]]
            tokens[s] = getattr(req, "_last_tok", 0)
        # NOTE: single shared `pos` per fused step; the simple engine keeps
        # per-slot positions aligned by admitting same-length prompts or by
        # per-slot stepping during prefill.  Fused decode uses max pos.
        pos = int(self.pos[active].max())
        with tr.span("serve.decode", lanes=len(active)) as sp:
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tokens), jnp.int32(pos)
            )
            nxt = np.asarray(jnp.argmax(logits, -1))
            sp.fence(self.cache)
        self.metrics.inc("decode_tokens", len(active))
        for s in active:
            rid = self.slots[s]
            req = self.running[rid]
            req.out.append(int(nxt[s]))
            req._last_tok = int(nxt[s])
            self.pos[s] = pos + 1
            if len(req.out) >= req.max_new or self.pos[s] >= self.s_max - 1:
                with tr.span("serve.retire", slot=s):
                    self._retire(s)

    def _tick_pipelined(self, tr) -> float:
        """Double-buffered tick: DISPATCH round N's fused decode (JAX async
        dispatch returns immediately), run round N+1's admit — prefix
        lookups, page allocation, publish rounds — on the host while the
        device works, then fence on the decode and retire.  Admitted
        requests join the decode from the NEXT tick (their prefill steps
        chain onto the in-flight cache, so per-slot KV stays exact).
        Returns the seconds of host work overlapped with the in-flight
        decode (0 when nothing was running)."""
        active = [s for s in range(self.max_batch) if self.slots[s] is not None]
        logits = None
        pos = 0
        if active:
            tokens = np.zeros(self.max_batch, np.int32)
            for s in active:
                tokens[s] = getattr(self.running[self.slots[s]], "_last_tok", 0)
            pos = int(self.pos[active].max())
            with tr.span("serve.decode.dispatch", lanes=len(active)):
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens), jnp.int32(pos)
                )
        t0 = time.perf_counter()
        with tr.span(
            "serve.admit", waiting=len(self.waiting), overlapped=bool(active)
        ):
            self._admit()
        overlap = time.perf_counter() - t0 if active else 0.0
        if logits is None:
            return 0.0
        with tr.span("serve.decode", lanes=len(active)) as sp:
            nxt = np.asarray(jnp.argmax(logits, -1))  # the fence: blocks here
            sp.fence(self.cache)
        self.metrics.inc("decode_tokens", len(active))
        for s in active:
            rid = self.slots[s]
            req = self.running[rid]
            req.out.append(int(nxt[s]))
            req._last_tok = int(nxt[s])
            self.pos[s] = pos + 1
            if len(req.out) >= req.max_new or self.pos[s] >= self.s_max - 1:
                with tr.span("serve.retire", slot=s):
                    self._retire(s)
        return overlap

    def _retire(self, slot: int):
        rid = self.slots[slot]
        req = self.running.pop(rid)
        req.t_done = time.time()
        self.done.append(req)
        self.metrics.inc("retired")
        self.slots[slot] = None
        self.kv.release(rid)
        # session churn: hot prompts get re-inserted by the next request —
        # eviction + re-publish of the same keys is the elimination workload
        chain = prefix_hashes(req.prompt)
        if chain and self.kv.used > self.kv.n_pages // 2:
            self.index.evict_batch([h for h, _ in chain])
        # session-range sweep: retired ids accumulate below the lowest live
        # id, so ONE fused scan+delete round clears them in bulk (the round
        # engine linearizes the scan before the same round's deletes;
        # amortized — no per-rid delete round at retire time).
        self._retired_since_sweep += 1
        if self._retired_since_sweep >= 8 or not self.running:
            # with nothing running, sweep past the highest id ever admitted
            # (the last retiree may have a lower rid than earlier ones)
            live_floor = min(self.running.keys(), default=self._max_rid + 1)
            if live_floor > self._evict_floor:
                self.sessions.evict_range(self._evict_floor, live_floor)
                self._evict_floor = live_floor
            self._retired_since_sweep = 0

    def drain_durability(self):
        """Flush both journals' pending commit groups and join any
        in-flight async commits — the engine-level persist fence (a
        no-op for volatile or non-grouped indexes)."""
        for h in (self.index.tree, self.sessions.tree):
            drain = getattr(h, "drain", None)
            if drain is not None:
                drain()

    def run_until_done(self, max_ticks: int = 10000):
        t = 0
        while (self.waiting or self.running) and t < max_ticks:
            self.tick()
            t += 1
        # workload done: pending groups would otherwise stay volatile until
        # the next tick that never comes
        self.drain_durability()
        return self.done

    def stats(self) -> dict:
        s = dict(self.index.stats())
        s["pages_used"] = self.kv.used
        s["session_scans"] = self.sessions.stats()["scans"]
        lat = [r.t_done - r.t_submit for r in self.done if r.t_done]
        s["n_done"] = len(self.done)
        s["mean_latency_s"] = float(np.mean(lat)) if lat else 0.0
        s["cache_hit_blocks"] = sum(r.cache_hit_blocks for r in self.done)
        s["ticks"] = self.metrics.value("ticks")
        s["tick_latency"] = self.metrics.histogram_summary("tick_latency_s")
        s["metrics"] = self.metrics.snapshot()
        s["index_metrics"] = self.index.tree.metrics.snapshot()
        s["recorder"] = self.recorder.snapshot()
        # durability degradation surface: present only when the indexes are
        # journaled; "degraded" is True if EITHER index's circuit breaker
        # is open (serving continues volatile, commits suspended).
        holders = [
            ("prefix", self.index.tree),
            ("sessions", self.sessions.tree),
        ]
        durable = {
            name: h.durability_status()
            for name, h in holders
            if hasattr(h, "durability_status")
        }
        if durable:
            durable["degraded"] = any(v["degraded"] for v in durable.values())
            s["durability"] = durable
        return s
