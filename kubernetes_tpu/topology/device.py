"""Device-side slice alignment Filter/Score (the oracle's jax twin).

Where the host oracle (topology/slices.py) loops per placement, the
kernel evaluates EVERY (orientation, anchor) placement of the whole
mesh at once with separable shifted reductions:

- feasibility: a box of shape (s0,s1,s2) anchored at `a` is free iff
  the per-axis window-ANDs of the free grid hold at `a` — s0+s1+s2
  shifts instead of prod(shape) gathers, wraparound via jnp.roll on a
  torus and zero-filled shifts on a walled mesh (a window crossing a
  wall reads False, which is exactly "infeasible anchor");
- fragmentation: the exposed-free-boundary count is a sum over the 6
  box faces, each face a window-sum of the free grid over the two
  orthogonal axes shifted one past the box along the third — the same
  halo cells the oracle walks, as three reused 2-axis prefix products;
- selection: score and the lowest-id tie rule pack into ONE int32 key,
  `(FRAG_CAP - frag) * A + (A-1 - pid)` for feasible placements and
  -1 otherwise, so the winner is a plain max — and the sharded
  variant is a shard-local max + `lax.pmax` over the placement axis,
  associative and therefore bit-identical at any shard count (the
  solver's cross-shard argmax contract, SURVEY §5.8).

Bit-identity with the oracle on (feasible, frag·feasible) and on the
selected placement is the differential contract
(tests/test_topology_slices.py); frag is reported 0 where infeasible
on both sides.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kubernetes_tpu.parallel.mesh import SLICE_AXIS
from kubernetes_tpu.topology.mesh import MeshSpec, orientations

#: compiled scan per (dims, wrap, orientations) signature.
_SCAN_CACHE: dict = {}
#: compiled sharded max per shard count.
_SHARDED_MAX_CACHE: dict = {}


def frag_cap(shape: Sequence[int]) -> int:
    """Exclusive upper bound on any placement's frag score (the box
    surface): the key packing needs it static."""
    s = tuple(shape) + (1,) * (3 - len(tuple(shape)))
    return 2 * (s[0] * s[1] + s[1] * s[2] + s[0] * s[2]) + 1


def _shift(g, k: int, axis: int, wrap: bool):
    """out[c] = g[c + k·e_axis]; torus wraps, mesh fills with zero
    (False) so windows crossing a wall read infeasible/absent."""
    if k == 0:
        return g
    r = jnp.roll(g, -k, axis=axis)
    if wrap:
        return r
    d = g.shape[axis]
    idx = jnp.arange(d)
    ok = (idx + k >= 0) & (idx + k < d)
    shape = [1, 1, 1]
    shape[axis] = d
    return jnp.where(ok.reshape(shape), r, jnp.zeros((), r.dtype))


def _win_and(g, s: int, axis: int, wrap: bool):
    acc = g
    for i in range(1, s):
        acc = acc & _shift(g, i, axis, wrap)
    return acc


def _win_sum(g, s: int, axis: int, wrap: bool):
    acc = g
    for i in range(1, s):
        acc = acc + _shift(g, i, axis, wrap)
    return acc


def _win_or_back(g, s: int, axis: int, wrap: bool):
    """OR over backward shifts: out[c] = OR_{i<s} g[c - i·e_axis]
    (the box dilation the coverage union needs)."""
    acc = g
    for i in range(1, s):
        acc = acc | _shift(g, -i, axis, wrap)
    return acc


def _build_scan(dims: tuple[int, int, int], wrap: bool,
                orients: tuple[tuple[int, int, int], ...], cap: int):
    cells = dims[0] * dims[1] * dims[2]
    A = len(orients) * cells

    def scan(free):
        """free: (d0,d1,d2) bool → (key (A,), covered (cells,) bool)."""
        free_i = free.astype(jnp.int32)
        keys = []
        covered = jnp.zeros(dims, dtype=jnp.bool_)
        for oi, (s0, s1, s2) in enumerate(orients):
            feas = _win_and(_win_and(_win_and(
                free, s0, 0, wrap), s1, 1, wrap), s2, 2, wrap)
            frag = jnp.zeros(dims, dtype=jnp.int32)
            # +x/-x faces: window-sum over (y,z), shifted past the box.
            ws_yz = _win_sum(_win_sum(free_i, s1, 1, wrap), s2, 2, wrap)
            if not (wrap and s0 == dims[0]):
                frag = frag + _shift(ws_yz, s0, 0, wrap) \
                    + _shift(ws_yz, -1, 0, wrap)
            ws_xz = _win_sum(_win_sum(free_i, s0, 0, wrap), s2, 2, wrap)
            if not (wrap and s1 == dims[1]):
                frag = frag + _shift(ws_xz, s1, 1, wrap) \
                    + _shift(ws_xz, -1, 1, wrap)
            ws_xy = _win_sum(_win_sum(free_i, s0, 0, wrap), s1, 1, wrap)
            if not (wrap and s2 == dims[2]):
                frag = frag + _shift(ws_xy, s2, 2, wrap) \
                    + _shift(ws_xy, -1, 2, wrap)
            pid = oi * cells + jnp.arange(cells, dtype=jnp.int32) \
                .reshape(dims)
            key = jnp.where(feas, (cap - frag) * A + (A - 1 - pid),
                            jnp.int32(-1))
            keys.append(key.reshape(-1))
            covered = covered | _win_or_back(_win_or_back(_win_or_back(
                feas, s0, 0, wrap), s1, 1, wrap), s2, 2, wrap)
        return jnp.concatenate(keys), covered.reshape(-1)

    return jax.jit(scan)


def device_scan(free_cells: np.ndarray, spec: MeshSpec,
                shape: Sequence[int]):
    """Run the kernel over one free mask. Returns
    (key (A,) int32, feas (A,) bool, frag (A,) int32, covered (cells,))
    as host arrays — None when the shape has no valid orientation or
    the int32 key packing would overflow (caller falls back to the
    host oracle; meshes that large are outside the device contract)."""
    orients = orientations(shape, spec)
    if not orients:
        return None
    cap = frag_cap(shape)
    A = len(orients) * spec.cells
    if cap * (A + 1) >= 2**31:
        return None
    sig = (spec.dims, spec.wrap, orients, cap)
    fn = _SCAN_CACHE.get(sig)
    if fn is None:
        fn = _SCAN_CACHE[sig] = _build_scan(
            spec.dims, spec.wrap, orients, cap)
    grid = jnp.asarray(
        np.asarray(free_cells, dtype=np.bool_).reshape(spec.dims))
    key_dev, covered_dev = fn(grid)
    key = np.asarray(key_dev)
    covered = np.asarray(covered_dev)
    feas = key >= 0
    frag = np.where(feas, cap - np.where(feas, key, 0) // A, 0) \
        .astype(np.int32)
    return key, feas, frag, covered


def decode_key(best_key: int, spec: MeshSpec,
               shape: Sequence[int]) -> tuple[int, int]:
    """Packed winner key → (placement id, frag); (-1, 0) = infeasible."""
    if best_key < 0:
        return -1, 0
    orients = orientations(shape, spec)
    A = len(orients) * spec.cells
    return A - 1 - int(best_key) % A, frag_cap(shape) - int(best_key) // A


def best_key(key: np.ndarray, shards: int | None = None) -> int:
    """Winner selection over the packed keys — shard-local max +
    cross-shard pmax when `shards` > 1 (parity-tested at {1,4,8})."""
    if len(key) == 0:
        return -1
    S = int(shards or 1)
    if S <= 1:
        return int(np.max(key))
    if S > len(jax.devices()):
        raise ValueError(
            f"requested {S} shards, have {len(jax.devices())} devices")
    pad = (-len(key)) % S
    padded = np.pad(key, (0, pad), constant_values=-1)
    fn = _SHARDED_MAX_CACHE.get(S)
    if fn is None:
        mesh = Mesh(np.array(jax.devices()[:S]), (SLICE_AXIS,))

        def local_max(block):
            return lax.pmax(jnp.max(block), SLICE_AXIS)

        fn = _SHARDED_MAX_CACHE[S] = jax.jit(shard_map(
            local_max, mesh=mesh, in_specs=P(SLICE_AXIS), out_specs=P(),
            check_vma=False))
    return int(fn(jnp.asarray(padded)))


def fragmentation_pct(free_cells: np.ndarray,
                      covered: np.ndarray) -> float:
    """Stranded-for-this-shape free capacity: the percentage of free
    cells no feasible placement covers (100 = every free cell is
    stranded; 0 = all free capacity still coalesces into slices)."""
    total = int(np.count_nonzero(free_cells))
    if total == 0:
        return 0.0
    return 100.0 * (1.0 - int(np.count_nonzero(covered)) / total)
