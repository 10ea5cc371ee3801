"""Continuously-batched prefill/decode serving engine.

Requests queue up (FIFO deque); the engine fills a fixed batch of decode
slots and recycles a slot as soon as its sequence finishes (EOS or max
tokens), keeping the decode batch full under churn. Admission is
CONTINUOUS and batched: every engine step takes as many queued requests
as there are free slots, groups them by prompt length, and prefills each
length group in ONE dispatch (each prefill writes all its slots' KV
ranges via the batched prefill step). Sampling is device-side — the
jitted steps return (B,) greedy token ids, so a decode step transfers B
int32s instead of the full (B, 1, vocab) logits array. Per-slot
positions support ragged sequence lengths inside one batch.

``st_mode`` routes the decode step's collectives — the new KV-cache row,
the sampled token ids, and (for MoE models) the hidden block — through
scheduled triggered-op programs of the ``"serve"`` pattern
(repro.serving.st_decode.STDecodeRouter): one cached schedule per
power-of-two active-slot bucket, token ids committed back THROUGH the
transport (bit-identical to the baseline path by construction), program
meta surfaced in :meth:`stats`. ``st_mode=None`` is the plain jitted
baseline.

Requests carry timestamps for the traffic generators (``time.monotonic()``):
``submitted_at`` (stamped by :meth:`ServingEngine.submit`),
``dequeued_at`` (taken off the queue for admission), ``admitted_at``
(its prefill returned), ``first_token_at`` (TTFT), ``done_at``
(completion). The engine records its host work as program spans
(:mod:`repro.runtime.spans`): ``engine.queued`` per request, keyed by
its id, from submission to dequeue (added to the span ring at dequeue
from the two stamps, so no profiler capture holds it); and
``engine.step`` per step, keyed by the step number, holding
``engine.admit`` (with ``engine.prefill`` per length group and
``engine.cache_merge``), ``engine.decode`` (the launch),
``engine.ids`` (the wait for the device and the ids' copy to the
host), ``engine.extract`` (the ST payload's extract and readback) and
``engine.commit`` (token bookkeeping); the router's ``router.dispatch``
runs between the last two.

``moe_impl`` names the MoE layers' implementation (``models/moe.py``):
"dense" by default, "ragged" for the dropless impl, whose form follows
a call's token count (``moe.routed_form``): every held expert on every
token for the decode step's few, the grouped matmul for a prefill's
many; :meth:`stats` gives the form of the decode shape and of each
prefill shape run (``moe_routed_form``). A MoE
model's steps count the assignments each held expert received from the
slots that serve a request (the batch's ``live`` rows), layer by layer;
:meth:`stats` gives them per held expert, summed over layers and every
prefill and decode call (``moe_held_assignments``). The steps also
return the experts each token chose: after :meth:`keep_routes` the
engine keeps them, on the device, for every request it serves, and
:meth:`routes` gives a request's.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cache_specs
from repro.models.model import decode_cache_write
from repro.models.moe import routed_form
from repro.models.params import is_spec
from repro.runtime import spans
from repro.train.steps import make_decode_sample_step, make_prefill_sample_step

_req_ids = itertools.count()


@dataclass
class Request:
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: int = -1                    # -1: never stop early
    req_id: int = field(default_factory=lambda: next(_req_ids))
    out_tokens: List[int] = field(default_factory=list)
    submitted_at: Optional[float] = None
    dequeued_at: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None


class ServingEngine:
    def __init__(self, cfg, params, rules, *, batch_slots: int = 4,
                 max_len: int = 256, moe_impl: str = "dense",
                 st_mode: Optional[str] = None, st_config="auto",
                 tuned_path: Optional[str] = None,
                 ranks_per_node: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.rules = rules
        self.B = batch_slots
        self.max_len = max_len
        self._prefill_sample = jax.jit(
            make_prefill_sample_step(cfg, rules, max_len=max_len,
                                     moe_impl=moe_impl))
        self._decode_sample = jax.jit(
            make_decode_sample_step(cfg, rules, moe_impl=moe_impl),
            donate_argnums=(2,))
        # how the decode step writes its K/V rows ("rows": in place into
        # the donated cache; "mask": the whole slice rewritten), fixed by
        # the rules the step is traced with
        self.decode_cache_write = decode_cache_write(cfg, rules)
        cspecs = cache_specs(cfg, batch_slots, max_len)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), cspecs, is_leaf=is_spec)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)
        self.queue: Deque[Request] = deque()
        self.completed: List[Request] = []
        self.prefill_dispatches = 0
        self.decode_steps = 0
        self.steps = 0
        self.tokens_generated = 0
        self.moe_impl = moe_impl
        self._prefill_lens = set()      # prompt lengths prefilled
        # per held expert, summed over layers and calls (MoE models)
        self.moe_held_assignments = None
        if cfg.moe is not None:
            self.moe_held_assignments = np.zeros(cfg.moe.held_range[1],
                                                 np.int64)
        # req_id -> [(chosen experts of a step, slot)], after keep_routes
        self._routes: Optional[Dict[int, list]] = None
        self.st_mode = st_mode
        self._router = None
        self._extract = None
        if st_mode is not None:
            from repro.serving.st_decode import STDecodeRouter
            self._kv_leaf = self._find_kv_leaf()
            moe = getattr(cfg, "moe", None) is not None
            self._router = STDecodeRouter(
                kv_dim=self._kv_leaf[2], d_model=cfg.d_model, moe=moe,
                slot_cap=batch_slots, mode=st_mode, config=st_config,
                tuned_path=tuned_path, ranks_per_node=ranks_per_node)
            self._extract = jax.jit(self._make_extractor())

    # -- ST payload extraction ------------------------------------------------
    def _find_kv_leaf(self):
        """Locate the first KV-cache leaf carrying the sequence axis:
        prefix-layer leaves are (B, max_len, ...), scanned-unit leaves
        carry a leading layer axis (L, B, max_len, ...). Returns
        (part, leaf index, flattened per-row payload width)."""
        for part, seq_axis in (("prefix", 1), ("unit", 2)):
            for i, lf in enumerate(jax.tree.leaves(self.cache[part])):
                if (lf.ndim > seq_axis and lf.shape[seq_axis] == self.max_len
                        and lf.shape[seq_axis - 1] == self.B):
                    width = int(np.prod(lf.shape[seq_axis + 1:], dtype=int))
                    return part, i, max(width, 1)
        raise ValueError("serving: no KV-cache leaf with a "
                         f"(batch, {self.max_len}) sequence axis found")

    def _make_extractor(self):
        part, idx, _ = self._kv_leaf

        def extract(cache, pos):
            """(B,) positions -> (B, width) f32: the cache rows the last
            decode step wrote, flattened — the per-slot KV payload the
            serve program mirrors to the replica's peers."""
            lf = jax.tree.leaves(cache[part])[idx]
            x = lf if part == "prefix" else lf[0]     # (B, max_len, ...)
            rows = x[jnp.arange(x.shape[0]), pos]
            return rows.reshape(x.shape[0], -1).astype(jnp.float32)

        return extract

    # -- admission ------------------------------------------------------------
    def submit(self, req: Request):
        req.submitted_at = time.monotonic()
        self.queue.append(req)

    def _free_slots(self):
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self):
        """Fill free slots from the queue: take requests FIFO, group by
        prompt length, and prefill each length group in ONE dispatch
        (the batched prefill writes every group slot's KV range)."""
        free = self._free_slots()
        if not free or not self.queue:
            return
        take: List[Request] = []
        while self.queue and len(take) < len(free):
            req = self.queue.popleft()
            now_ns = time.monotonic_ns()
            req.dequeued_at = now_ns / 1e9
            # a request appended to ``queue`` directly was never stamped
            if req.submitted_at is not None:
                spans.record("engine.queued", round(req.submitted_at * 1e9),
                             now_ns, req.req_id)
            take.append(req)
        groups: Dict[int, List[Request]] = {}
        for req in take:
            groups.setdefault(len(req.prompt), []).append(req)
        free_iter = iter(free)
        for L in sorted(groups):
            reqs = groups[L]
            slots = [next(free_iter) for _ in reqs]
            with spans.span("engine.prefill",
                            tuple(r.req_id for r in reqs)):
                toks = np.zeros((self.B, L), np.int32)
                for slot, req in zip(slots, reqs):
                    toks[slot] = req.prompt
                batch = {"tokens": jnp.asarray(toks),
                         "positions": jnp.broadcast_to(
                             jnp.arange(L, dtype=jnp.int32), (self.B, L))}
                if self.cfg.family == "vlm":
                    batch["vision"] = jnp.zeros(
                        (self.B, self.cfg.vision.num_tokens,
                         self.cfg.vision.raw_dim), jnp.float32)
                if self.cfg.moe is not None:
                    batch["live"] = self._live(slots)
                ids, new_cache, *routing = self._prefill_sample(
                    self.params, batch)
                self.prefill_dispatches += 1
                self._prefill_lens.add(L)
            # merge ONLY the group's cache rows (other slots keep
            # theirs). prefix-layer leaves are (B, ...); scanned-unit
            # leaves carry a leading layer axis (L, B, ...), so batch is
            # dim 1 there.
            with spans.span("engine.cache_merge"):
                idx = jnp.asarray(np.array(slots, np.int32))
                self.cache = {
                    "prefix": jax.tree.map(
                        lambda old, new: old.at[idx].set(new[idx]),
                        self.cache["prefix"], new_cache["prefix"]),
                    "unit": jax.tree.map(
                        lambda old, new: old.at[:, idx].set(new[:, idx]),
                        self.cache["unit"], new_cache["unit"]),
                }
            # the wait for the prefill, in engine.admit's own time
            ids_np = np.asarray(ids)
            self._take_routing(routing, zip(slots, reqs))
            now = time.monotonic()
            for slot, req in zip(slots, reqs):
                req.out_tokens.append(int(ids_np[slot]))
                req.admitted_at = now
                req.first_token_at = now
                self.slot_req[slot] = req
                self.slot_pos[slot] = L
                self.tokens_generated += 1
                # a one-token (or instant-EOS) request completes at
                # admission — don't hold a decode slot for it
                if (len(req.out_tokens) >= req.max_new_tokens
                        or req.out_tokens[-1] == req.eos_id
                        or self.slot_pos[slot] >= self.max_len - 1):
                    req.done_at = now
                    self.completed.append(req)
                    self.slot_req[slot] = None

    # -- decode loop ----------------------------------------------------------
    def _active(self):
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def step(self):
        """One engine step: admit, batched decode, recycle finished slots."""
        self.steps += 1
        with spans.span("engine.step", self.steps - 1):
            return self._step()

    def _step(self):
        with spans.span("engine.admit"):
            self._admit()
        active = self._active()
        if not active:
            return 0
        toks = np.zeros((self.B, 1), np.int32)
        for i in active:
            toks[i, 0] = self.slot_req[i].out_tokens[-1]
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(self.slot_pos[:, None])}
        if self.cfg.family == "vlm":
            batch["vision"] = jnp.zeros(
                (self.B, self.cfg.vision.num_tokens,
                 self.cfg.vision.raw_dim), jnp.float32)
        if self.cfg.moe is not None:
            batch["live"] = self._live(active)
        pos_written = self.slot_pos.copy()      # rows this decode writes
        with spans.span("engine.decode"):
            ids, hid, self.cache, *routing = self._decode_sample(
                self.params, batch, self.cache)
        self.decode_steps += 1
        with spans.span("engine.ids"):
            ids_np = np.asarray(ids)
            self._take_routing(routing,
                               [(i, self.slot_req[i]) for i in active])
        if self._router is not None:
            act = np.asarray(active, np.int32)
            with spans.span("engine.extract"):
                payload = np.asarray(
                    self._extract(self.cache, jnp.asarray(pos_written)))[act]
                hid_np = (np.asarray(hid)[act]
                          if self._router.moe_on else None)
            committed, _, _ = self._router.dispatch(payload, ids_np[act],
                                                    hid=hid_np)
            # the transported ids are authoritative: serving reads its
            # tokens off the committed window buffer
            ids_np = ids_np.copy()
            ids_np[act] = committed
        with spans.span("engine.commit"):
            for i in active:
                req = self.slot_req[i]
                nxt = int(ids_np[i])
                req.out_tokens.append(nxt)
                self.tokens_generated += 1
                self.slot_pos[i] += 1
                done = (len(req.out_tokens) >= req.max_new_tokens
                        or nxt == req.eos_id
                        or self.slot_pos[i] >= self.max_len - 1)
                if done:
                    req.done_at = time.monotonic()
                    self.completed.append(req)
                    self.slot_req[i] = None
        return len(active)

    def _live(self, slots):
        live = np.zeros(self.B, bool)
        live[slots] = True
        return jnp.asarray(live)

    def _take_routing(self, routing, served):
        """A MoE step's (layers, held) load into the per-expert sums, and
        after :meth:`keep_routes` its chosen experts kept for each
        (slot, request) it ``served``."""
        if not routing:
            return
        load, sel = routing
        self.moe_held_assignments += np.asarray(load).sum(0)
        if self._routes is not None:
            for slot, req in served:
                self._routes.setdefault(req.req_id, []).append((sel, slot))

    def keep_routes(self):
        """From now on keep the experts every served position chose (a
        MoE model's; the arrays stay on the device until :meth:`routes`
        reads them)."""
        self._routes = {}

    def routes(self, req: Request) -> np.ndarray:
        """(positions, MoE layers, top_k) int32: the experts chosen at
        each position the engine fed for ``req`` since
        :meth:`keep_routes`, in order: the prompt's, then each decode
        step's input token's."""
        K = self.cfg.moe.top_k
        parts = []
        for sel, slot in (self._routes or {}).get(req.req_id, []):
            a = np.asarray(sel)                 # (B * S, layers * K)
            parts.append(a.reshape(self.B, -1, a.shape[1] // K, K)[slot])
        if not parts:
            return np.zeros((0, 0, K), np.int32)
        return np.concatenate(parts).astype(np.int32)

    def run_until_drained(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or self._active()) and steps < max_steps:
            self.step()
            steps += 1
        return steps

    # -- reporting ------------------------------------------------------------
    def stats(self) -> dict:
        d = {"batch_slots": self.B, "max_len": self.max_len,
             "queued": len(self.queue), "active": len(self._active()),
             "completed": len(self.completed),
             "prefill_dispatches": self.prefill_dispatches,
             "decode_steps": self.decode_steps,
             "tokens_generated": self.tokens_generated,
             "st_mode": self.st_mode,
             "decode_cache_write": self.decode_cache_write}
        if self.moe_held_assignments is not None:
            d["moe_impl"] = self.moe_impl
            if self.moe_impl == "ragged":
                d["moe_routed_form"] = {
                    "decode": routed_form(self.B),
                    "prefill": {L: routed_form(self.B * L)
                                for L in sorted(self._prefill_lens)}}
            d["moe_held_assignments"] = self.moe_held_assignments.tolist()
        if self._router is not None:
            d["st"] = self._router.stats()
        return d
