"""Faces: 26-neighbor 3-D halo exchange (paper §6.2).

Weak-scaling Nekbone-style nearest-neighbor pattern: each rank owns an
(nx, ny, nz) block of spectral-element surface data and exchanges faces
(6), edges (12) and corners (8) with its 26 neighbors on a periodic
(px, py, pz) process grid.

This module provides the domain logic used by the ST stream programs and
the benchmarks:
  * DIRECTIONS          — the 26 neighbor offsets
  * pack / unpack       — surface extraction/injection (merged jnp kernel;
                          kernels/halo_pack provides the Pallas variant)
  * increment / compare — the paper's compute kernels around the exchange
  * build_faces_program — enqueues one full Faces iteration on an STStream
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.patterns import PatternTopology, register_pattern

DIRECTIONS: List[Tuple[int, int, int]] = [
    (dx, dy, dz)
    for dx in (-1, 0, 1) for dy in (-1, 0, 1) for dz in (-1, 0, 1)
    if (dx, dy, dz) != (0, 0, 0)
]


def surface_slices(n: Tuple[int, int, int], d: Tuple[int, int, int]):
    """Index slices of the local block that go to neighbor d.
    Face: a 1-thick slab; edge: 1x1xn pencil; corner: single cell."""
    out = []
    for dim, (nd, dd) in enumerate(zip(n, d)):
        if dd == -1:
            out.append(slice(0, 1))
        elif dd == 1:
            out.append(slice(nd - 1, nd))
        else:
            out.append(slice(0, nd))
    return tuple(out)


def surface_size(n, d) -> int:
    return int(np.prod([1 if dd != 0 else nd for nd, dd in zip(n, d)]))


def pack_ref(field, n, directions=DIRECTIONS):
    """field: (R, nx, ny, nz) local view (R=1 under shard_map).
    Returns flat (R, total) buffer with each direction's surface
    concatenated — the MERGED pack kernel (paper §5.4)."""
    parts = []
    for d in directions:
        sl = (slice(None),) + surface_slices(n, d)
        parts.append(field[sl].reshape(field.shape[0], -1))
    return jnp.concatenate(parts, axis=1)


def pack_one(field, n, d):
    sl = (slice(None),) + surface_slices(n, d)
    return field[sl].reshape(field.shape[0], -1)


def unpack_ref(halo_in: Dict, n, directions=DIRECTIONS):
    """Sum all received surfaces into an accumulator block (Nekbone adds
    contributions on shared faces/edges/corners).
    halo_in: {direction: (R, surface)} received buffers."""
    R = next(iter(halo_in.values())).shape[0]
    acc = jnp.zeros((R,) + tuple(n), jnp.float32)
    for d, buf in halo_in.items():
        # data from neighbor d lands on OUR face toward d
        sl = (slice(None),) + surface_slices(n, d)
        shp = (R,) + tuple(1 if dd != 0 else nd for nd, dd in zip(n, d))
        acc = acc.at[sl].add(buf.reshape(shp).astype(jnp.float32))
    return acc


def offsets_of(n, directions=DIRECTIONS):
    offs, cur = {}, 0
    for d in directions:
        s = surface_size(n, d)
        offs[d] = (cur, s)
        cur += s
    return offs, cur


def make_faces_kernels(n):
    """Iteration-stable kernel closures (created once per program; the same
    function objects are enqueued every iteration so per-op executables are
    compiled once, like preloaded GPU kernels)."""
    offs, _total = offsets_of(n)

    def increment(src, it):
        return src + 1.0 + jnp.mod(it, 3.0), it + 1.0

    def pack_all(src):
        flat = pack_ref(src, n)
        return tuple(flat[:, o:o + s]
                     for d, (o, s) in ((d, offs[d]) for d in DIRECTIONS))

    packs = {}
    unpacks = {}
    for d in DIRECTIONS:
        def pack_d(src, d=d):
            return pack_one(src, n, d)
        packs[d] = pack_d

        def unpack_d(acc, r, d=d):
            return acc.at[(slice(None),) + surface_slices(n, d)].add(
                r.reshape((acc.shape[0],)
                          + tuple(1 if dd != 0 else nd
                                  for nd, dd in zip(n, d))))
        unpacks[d] = unpack_d

    def unpack_compare(src, *recvs):
        hal = {d: r for d, r in zip(DIRECTIONS, recvs)}
        acc = unpack_ref(hal, n)
        res = jnp.max(jnp.abs(acc))[None]
        return acc, res

    def zero_acc(acc):
        return jnp.zeros_like(acc)

    def compare(acc):
        return jnp.max(jnp.abs(acc))[None]

    return {"increment": increment, "pack_all": pack_all, "packs": packs,
            "unpacks": unpacks, "unpack_compare": unpack_compare,
            "zero_acc": zero_acc, "compare": compare}


def compare_kernel():
    """Returns residual between received halo accumulation and its expected
    value; benchmark asserts it's finite (stands in for Faces' verify)."""
    def fn(acc, expected):
        return jnp.abs(acc - expected).max(axis=tuple(range(1, acc.ndim)),
                                           keepdims=False)[..., None]
    return fn


def faces_reference(src0, grid, n, niter):
    """Plain numpy replay of ``niter`` Faces iterations on a periodic
    ``grid`` — the oracle the executors are checked against. ``src0`` is
    (num_ranks, *n) with ranks row-major over the grid (the linearization
    of ``STStream.rank_strides``). Each iteration increments every block
    by ``1 + it % 3``, then every rank sums the surface each of its 26
    neighbours sends toward it into a fresh accumulator. Returns
    (src, acc) after the last iteration, both (num_ranks, *n)."""
    grid, n = tuple(grid), tuple(n)
    src = np.asarray(src0, np.float32).reshape(grid + n)
    acc = np.zeros_like(src)
    for it in range(niter):
        src = src + np.float32(1.0 + it % 3)
        acc = np.zeros_like(src)
        for d in DIRECTIONS:
            # rank r receives what rank r - d sent in direction d
            sl = (Ellipsis,) + surface_slices(n, d)
            acc[sl] += np.roll(src, d, axis=(0, 1, 2))[sl]
    num_ranks = int(np.prod(grid))
    return src.reshape((num_ranks,) + n), acc.reshape((num_ranks,) + n)


# ---------------------------------------------------------------------------
# Program builders
# ---------------------------------------------------------------------------

def faces_topology(grid_axes=("x", "y", "z"),
                   ranks_per_node=None) -> PatternTopology:
    """26-neighbor halo group; opposite = component-wise negation.
    ``ranks_per_node`` maps consecutive linear ranks onto hardware nodes
    so lowering can tag each direction's put intra- vs inter-node."""
    return PatternTopology("faces", tuple(grid_axes),
                           tuple(DIRECTIONS),
                           ranks_per_node=ranks_per_node)


def create_faces_window(stream, n, name="faces", extra_buffers=None,
                        double_buffer=False, ranks_per_node=None):
    """Window with: src block, halo recv buffer per direction, accumulator,
    and an iteration counter so kernels are iteration-independent (the host
    baseline must not recompile per iteration). ``double_buffer`` gives
    every send/recv surface (and the signal counters) a ping/pong pair so
    alternating epochs never touch the same communication buffers."""
    bufs = {"src": (tuple(n), jnp.float32),
            "acc": (tuple(n), jnp.float32),
            "it": ((1,), jnp.float32),
            "res": ((1,), jnp.float32)}
    db_names = []
    for d in DIRECTIONS:
        bufs[f"recv{d[0]}{d[1]}{d[2]}"] = ((surface_size(n, d),), jnp.float32)
        bufs[f"send{d[0]}{d[1]}{d[2]}"] = ((surface_size(n, d),), jnp.float32)
        db_names += [f"recv{d[0]}{d[1]}{d[2]}", f"send{d[0]}{d[1]}{d[2]}"]
    if extra_buffers:
        bufs.update(extra_buffers)
    return stream.create_window(
        name, bufs, DIRECTIONS,
        topology=faces_topology(stream.grid_axes,
                                ranks_per_node=ranks_per_node),
        double_buffer=double_buffer, db_names=db_names)


def enqueue_faces_iteration(stream, win, n, kernels, merged=True, phase=0):
    """One inner-loop Faces iteration (paper Fig. 9b structure):
    post -> increment kernel -> start -> 26 puts -> complete -> wait ->
    unpack+compare kernel. All enqueued; nothing executes until
    synchronize(). `kernels` from make_faces_kernels(n). ``phase`` picks
    the ping/pong buffer+counter set on a double-buffered window."""
    def q(b):
        return win.qual(b, phase)

    stream.post(win, phase=phase)
    stream.launch(kernels["increment"], [q("src"), q("it")],
                  [q("src"), q("it")], label="increment")
    # pack kernel(s): merged = ONE launch extracting all 26 surfaces
    if merged:
        stream.launch(kernels["pack_all"], [q("src")],
                      [q(f"send{d[0]}{d[1]}{d[2]}") for d in DIRECTIONS],
                      label="pack_merged")
    else:
        for d in DIRECTIONS:
            stream.launch(kernels["packs"][d], [q("src")],
                          [q(f"send{d[0]}{d[1]}{d[2]}")],
                          label=f"pack{d}")
    stream.start(win, phase=phase)
    for d in DIRECTIONS:
        stream.put(win, q(f"send{d[0]}{d[1]}{d[2]}"),
                   q(f"recv{d[0]}{d[1]}{d[2]}"), d, phase=phase)
    stream.complete(win, phase=phase)
    stream.wait(win, phase=phase)

    names = [f"recv{d[0]}{d[1]}{d[2]}" for d in DIRECTIONS]
    if merged:
        stream.launch(kernels["unpack_compare"],
                      [q("src")] + [q(x) for x in names],
                      [q("acc"), q("res")], label="unpack_merged")
    else:
        stream.launch(kernels["zero_acc"], [q("acc")], [q("acc")],
                      label="zero_acc")
        for d, nm in zip(DIRECTIONS, names):
            stream.launch(kernels["unpacks"][d], [q("acc"), q(nm)],
                          [q("acc")], label=f"unpack{d}")
        stream.launch(kernels["compare"], [q("acc")], [q("res")],
                      label="compare")


def build_faces_program(stream, n, niter, merged=True, kernels=None,
                        host_sync_every=0, extra_buffers=None,
                        overlap_kernel=None, name="faces",
                        double_buffer=False, ranks_per_node=None):
    """Enqueue the FULL Faces benchmark program: window + kernels + niter
    inner-loop iterations. ``host_sync_every=k`` inserts an application-
    level host_sync() every k iterations (paper §5.2.1 throttling — each
    chunk becomes its own compiled segment). ``overlap_kernel`` enqueues
    an independent compute launch per iteration (paper §6.7); it runs on
    a buffer from ``extra_buffers``. ``double_buffer`` alternates epochs
    over ping/pong send/recv+counter sets so a multi-stream schedule
    (``nstreams>1``) can run epoch e+1's transfers during epoch e's
    compute. ``ranks_per_node`` sets the hardware node mapping on the
    window topology: each direction's put lowers with an intra/inter
    link tag. With ``pack`` scheduling (schedule.pack_puts) the epoch's
    multi-face groups aggregate: every set of off-node directions whose
    rank permutations coincide (on a size-2 periodic axis +1 and -1 are
    the SAME shift, so e.g. on a (2,2,2) grid with ranks_per_node=4 the
    18 off-node surface puts ride 4 packed descriptors, one per moved
    axis set) becomes one packed multi-buffer descriptor.
    Returns (window, kernels)."""
    stream.pattern = stream.pattern or "faces"
    win = create_faces_window(stream, n, name=name,
                              extra_buffers=extra_buffers,
                              double_buffer=double_buffer,
                              ranks_per_node=ranks_per_node)
    kernels = kernels or make_faces_kernels(n)
    for it in range(niter):
        enqueue_faces_iteration(stream, win, n, kernels, merged=merged,
                                phase=(it % 2 if double_buffer else 0))
        if overlap_kernel is not None:
            fn, buf = overlap_kernel
            stream.launch(fn, [win.qual(buf)], [win.qual(buf)],
                          label="overlap")
        if host_sync_every and (it + 1) % host_sync_every == 0 \
                and it + 1 < niter:
            stream.host_sync()
    return win, kernels


@register_pattern("faces", grid_axes=("x", "y", "z"),
                  default_grid=(2, 2, 2),
                  doc="26-neighbor 3-D halo exchange (paper §6.2)")
def _faces_pattern(stream, niter, *, n=(4, 4, 4), merged=True,
                   host_sync_every=0, **kw):
    return build_faces_program(stream, tuple(n), niter, merged=merged,
                               host_sync_every=host_sync_every, **kw)
