"""ST decode router: the serving engine's per-step collectives on the
triggered-op pipeline.

Every decode step of a continuously-batched engine moves (per active
slot) one KV-cache row, one sampled token id, and — for MoE models —
one hidden block to the replica's peers. The router runs that movement
through a scheduled ``TriggeredProgram`` of the ``"serve"`` pattern
(repro.core.serve_decode) instead of per-step host-orchestrated
transfers:

  * programs are built and scheduled ONCE per power-of-two active-slot
    bucket (``autotune.slot_bucket``) and cached — ragged decode
    batches reuse the cached schedule, and the tuned-config cache is
    consulted per bucket under the ``("serve", grid, rpn, "b<bucket>")``
    key when ``config="auto"``;
  * each dispatch stages the payloads into the persistent window state,
    runs ONE ``synchronize`` (mode ``"st"``: a single compiled program;
    ``"host"``: the per-descriptor baseline; ``"fused"``: the
    device-resident progress engine), and reads the engine's sampled
    token ids back from the COMMITTED ``outtok`` buffer — the transport
    is load-bearing, so a schedule or delivery defect changes served
    tokens and the bit-identity tests catch it;
  * payloads are replicated across ranks (each serving replica stands
    for one rank of the decode collective), so the committed buffers
    are bit-identical to the staged ones by construction — the
    ST-vs-baseline equality the acceptance tests pin down.

``stats()`` exposes the scheduled program meta per bucket (descriptor
counts, puts/epoch, segments, config label, dispatch count) — this is
what surfaces in ``ServingEngine`` serving stats and the bench's
serving table.

Each dispatch is the program span ``router.dispatch``
(:mod:`repro.runtime.spans`), holding ``router.stage`` (padding, the
payloads' ``device_put``, the counter resets), the serve program's
``st.synchronize`` and ``router.readback`` (the committed buffers'
copies to the host).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import ScheduleConfig, resolve_config, slot_bucket
from repro.core.compat import make_mesh
from repro.core.patterns import get_pattern
from repro.core.stream import STStream
from repro.runtime import spans

_MODES = ("st", "host", "fused")


@dataclasses.dataclass
class _BucketEntry:
    """One cached scheduled program + persistent window state."""
    stream: STStream
    win: object
    state: dict
    config: Optional[ScheduleConfig]
    meta: dict
    programs: list
    dispatches: int = 0


class STDecodeRouter:
    """Routes decode-step payloads through scheduled serve programs,
    one cached entry per active-slot bucket."""

    def __init__(self, *, kv_dim: int, d_model: int = 0, moe: bool = False,
                 slot_cap: int = 0, mode: str = "st", config="auto",
                 tuned_path: Optional[str] = None,
                 ndev: Optional[int] = None,
                 ranks_per_node: Optional[int] = None,
                 dtype=jnp.float32):
        if mode not in _MODES:
            raise ValueError(f"st_mode must be one of {_MODES}, got {mode!r}")
        self.kv_dim = int(kv_dim)
        self.d_model = int(d_model)
        self.slot_cap = int(slot_cap)
        self.mode = mode
        self.config = config
        self.tuned_path = tuned_path
        self.ranks_per_node = ranks_per_node
        self.dtype = dtype
        self.ndev = int(ndev) if ndev else jax.device_count()
        # the builder degrades moe to the plain KV ring on one rank
        self.moe = bool(moe) and self.d_model > 0
        self.moe_on = self.moe and self.ndev > 1
        self.mesh = make_mesh((self.ndev,), ("data",))
        self._entries: Dict[int, _BucketEntry] = {}

    # -- program cache --------------------------------------------------------
    def _resolve(self, bucket: int) -> Optional[ScheduleConfig]:
        spec = resolve_config(self.config, "serve", grid=(self.ndev,),
                              ranks_per_node=self.ranks_per_node,
                              size=f"b{bucket}", path=self.tuned_path,
                              slots=bucket, kv_dim=self.kv_dim,
                              d_model=self.d_model, moe=self.moe)
        if spec is not None and self.mode == "fused" and not spec.fused:
            # mode="fused" implies fused scheduling; a tuned config that
            # predates (or pruned) the knob must not undo it
            spec = dataclasses.replace(spec, fused=True)
        return spec

    def _entry(self, bucket: int) -> _BucketEntry:
        e = self._entries.get(bucket)
        if e is not None:
            return e
        spec = self._resolve(bucket)
        stream = STStream(self.mesh, ("data",))
        build_kw = dict(slots=bucket, kv_dim=self.kv_dim,
                        d_model=self.d_model, moe=self.moe,
                        dtype=self.dtype,
                        ranks_per_node=self.ranks_per_node)
        if spec is not None:
            ov = spec.build_overrides()
            ov.pop("multicast", None)       # serve has no multicast knob
            build_kw.update(ov)
        win, _ = get_pattern("serve").build(stream, 1, **build_kw)
        state = stream.allocate()
        sched_kw = spec.sched_kwargs() if spec is not None else {}
        if self.mode == "fused":
            sched_kw["fused"] = True
        progs = stream.scheduled_programs(**sched_kw)
        meta = dict(progs[0].stats(), bucket=bucket, mode=self.mode,
                    ndev=self.ndev, moe=self.moe_on,
                    config=spec.label() if spec is not None else "default")
        e = _BucketEntry(stream=stream, win=win, state=state, config=spec,
                         meta=meta, programs=progs)
        self._entries[bucket] = e
        return e

    # -- dispatch -------------------------------------------------------------
    def _stage(self, e: _BucketEntry, name: str, arr, shape, dtype):
        """Pad a (A, ...) payload to the bucket, replicate it across the
        ranks, and land it in the persistent window state."""
        buf = np.zeros(shape, np.dtype(dtype))
        a = np.asarray(arr)
        buf[:a.shape[0]] = a
        rep = jnp.broadcast_to(jnp.asarray(buf)[None],
                               (self.ndev,) + tuple(shape))
        key = e.win.qual(name)
        e.state[key] = jax.device_put(rep, e.state[key].sharding)

    def dispatch(self, kv_rows, tok_ids, hid=None):
        """Run one decode access epoch. ``kv_rows`` (A, kv_dim) is the
        step's new KV-cache rows, ``tok_ids`` (A,) int32 the device-
        sampled token ids, ``hid`` (A, d_model) the hidden block for
        MoE dispatch (required when the router was built with moe on a
        multi-rank grid). Returns ``(tok, mirror, hmir)`` read back
        from the COMMITTED window buffers, truncated to A rows (hmir is
        None without MoE dispatch)."""
        with spans.span("router.dispatch"):
            with spans.span("router.stage"):
                A = int(np.asarray(tok_ids).shape[0])
                bucket = slot_bucket(A, self.slot_cap)
                e = self._entry(bucket)
                self._stage(e, "kv", kv_rows, (bucket, self.kv_dim),
                            self.dtype)
                self._stage(e, "tok", tok_ids, (bucket,), np.int32)
                if self.moe_on:
                    if hid is None:
                        raise ValueError(
                            "dispatch: hid payload required with moe")
                    self._stage(e, "hid", hid, (bucket, self.d_model),
                                self.dtype)
                # the persistent counters accumulate across dispatches;
                # reset them so every epoch starts from the program's
                # expected zeros
                for cname in e.win.counter_names():
                    cur = e.state[cname]
                    e.state[cname] = jax.device_put(
                        jnp.zeros(cur.shape, cur.dtype), cur.sharding)
            sync_kw = dict(mode=self.mode, donate=False)
            if e.config is not None:
                sync_kw["config"] = e.config
            e.state = e.stream.synchronize(e.state, **sync_kw)
            e.dispatches += 1
            with spans.span("router.readback"):
                q = e.win.qual
                names = ("outtok", "mirror") + (("hmir",) if self.moe_on
                                                else ())
                got = [np.asarray(e.state[q(n)]) for n in names]
            tok, mirror = got[0][0, :A], got[1][0, :A]
            hmir = got[2][0, :A] if self.moe_on else None
            return tok, mirror, hmir

    # -- reporting ------------------------------------------------------------
    def stats(self) -> dict:
        return {"pattern": "serve", "mode": self.mode, "ndev": self.ndev,
                "moe": self.moe_on,
                "buckets": {b: dict(e.meta, dispatches=e.dispatches,
                                    fusion_backend=e.programs[0].meta.get(
                                        "fusion_backend"))
                            for b, e in sorted(self._entries.items())}}
