"""Pallas fused wavefront solve kernel.

The lax.scan wavefront step (ops/solver.py `_rescoring_wave_scan`) emits
a CHAIN of small XLA ops per wave — class-plane gather, bit-mask unpack,
fit/balanced scoring, prefix-distinct argmax, (W,W) conflict re-score,
capacity debit — with the carry bouncing through HBM between them. The
whole working set fits VMEM at production chunk shapes ((C,N/8) bit mask
+ (C,N) class planes at C ≤ 31 is ~25 KB/chunk at 50k nodes, plus a
W ≤ 64 register-resident conflict block), so this module fuses ONE wave
step into ONE Pallas grid step with the used-state carry resident:

    grid = (K, n_waves)        # K multistart orders, waves innermost
    step(k, i):
        carry  = per-k output blocks (free_q / free_pods / used_nz),
                 seeded from the chunk state at i == 0 and persisted
                 across grid steps (index map constant in i)
        fused  = unpack packed mask bits -> gather class planes ->
                 fit/balanced score -> prefix-distinct wave argmax ->
                 pairwise (W,W) conflict re-score -> capacity debit

Bit-identity contract: the kernel body runs the SAME op sequence as the
scan's `wave_step` — it calls the identical `ops/kernels.py` score
functions and the identical `_wave_spec_picks`/`_wave_conflicts` helpers
from ops/solver.py on values read from refs — so assignments are
bit-identical to the lax.scan reference at every wave width, strategy,
and class-plane shape. The scan REMAINS the semantic reference, and as
of PR 21 it is also the only form that runs on a device: neither kernel
in this module lowers through Mosaic (see `resolve_mode` for the
compiler's own words), so `KTPU_PALLAS=auto` routes off on every
platform and the kernels are exercised by the CPU interpret-mode suites
only. A chunk the flag wants on the kernel but whose shape it does not
fuse keeps its scan, counted in `solver_pallas_fallbacks_total`.

Unsupported shapes, stated honestly: the kernel holds the full (C,N)
planes and the (W,N) wave evaluation in one grid step, so chunks whose
working set exceeds `MAX_STATE_BYTES` fall back to the scan until an
N-blocked variant exists. Spread, shortlist, and the Sinkhorn optimal
mode keep their scan forms (each is a different fusion shape); the
router counts each as a distinct fallback reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from kubernetes_tpu.ops import kernels
from kubernetes_tpu.ops import solver

NEG_INF = -jnp.inf

#: per-grid-step working-set ceiling (bytes). The fused step keeps the
#: unpacked (C,N) mask, the (C,N) score plane, the (W,N) evaluation
#: block, and the (N,R) carries resident at once; chunks above this
#: fall back to the scan with reason="shape". Bounds the interpreter's
#: host memory only: it was never derived from a chip's VMEM (a v5e
#: core has ~16 MiB), so a kernel that lowers must re-derive it.
MAX_STATE_BYTES = 128 * 1024 * 1024


def state_bytes(n_nodes: int, n_classes: int, n_res: int,
                wave_w: int) -> int:
    """Estimate of one grid step's resident working set."""
    planes = n_classes * n_nodes * 5          # bool mask + f32 scores
    wave = wave_w * n_nodes * 9               # fits/sc/masked blocks
    carry = n_nodes * n_res * 16 + n_nodes * 8
    return planes + wave + carry


def unsupported_reason(n_nodes: int, n_classes: int, n_res: int,
                       wave_w: int) -> str | None:
    """Structural shape gate: None = the kernel supports this chunk,
    else the scan-fallback reason for `solver_pallas_fallbacks_total`."""
    if wave_w < 2:
        return "wave_off"
    if n_nodes < 1 or n_classes < 1:
        return "shape"
    if state_bytes(n_nodes, n_classes, n_res, wave_w) > MAX_STATE_BYTES:
        return "shape"
    return None


def resolve_mode(flag: str, platform: str) -> str:
    """KTPU_PALLAS value -> 'off' | 'interpret' | 'compiled' on `platform`
    (`jax.default_backend()`): the one copy of the policy, read by the
    router (`AdaptiveTuner.pallas_mode`) and by `solve_provenance`.

    | flag        | cpu         | any other platform            |
    |-------------|-------------|-------------------------------|
    | `off`       | off         | off                           |
    | `auto`      | off         | off                           |
    | `interpret` | interpret   | ValueError (CPU test mode)    |
    | `on`        | compiled    | compiled                      |

    `auto` is off everywhere BY POLICY, not by probe: the real kernels
    were lowered for the TPU v5e (jax 0.9.0 / libtpu 0.0.34, PR 21) and
    Mosaic refused both. `wave_solve` at N=5120, P=1024, W=32: "The
    Pallas TPU lowering currently requires that the last two dimensions
    of your block shape are divisible by 8 and 128 respectively, or be
    equal to the respective dimensions of the overall array. Block spec
    for args[2] in pallas_call _wave_step_kernel ... has block shape
    (Blocked(block_size=1), Blocked(block_size=1),
    Blocked(block_size=32)), array shape (1, 32, 32)". With every block
    made full-array (the small repair) the next refusal is the class-
    plane row gather `mask[row]`: "ValueError: Shape mismatch in input,
    indices and output" (Mosaic lowers only take_along_axis-shaped 2-D
    gathers), which is also what `wave_eval` dies on; behind it wait
    the `[row[:, None], safe[None, :]]` gather, the `.at[safe].add`
    scatters, bool refs and the in-kernel cond/fori_loop replay. That
    is a rewrite of the op sequence the bit-identity contract rests on
    (ROADMAP C3), not a repair.

    `on` therefore means "compile the real kernel or fail": the router
    answers `compiled`, the fused program's first compile at the
    chunk's own static shape raises the compiler's error, and the run
    fails — on CPU too, where pallas has no compiled lowering. It
    never degrades to interpret. `interpret` runs the kernel body
    through the Pallas interpreter, which is how the CPU suites
    validate it; asking for it on an accelerator is an error."""
    if flag in ("off", "auto"):
        return "off"
    if flag == "interpret":
        if platform != "cpu":
            raise ValueError(
                "KTPU_PALLAS=interpret is a CPU test mode; refused on "
                f"platform {platform!r}")
        return "interpret"
    return "compiled"


# ---------------------------------------------------------------------------
# fused wave-step solve: the whole wavefront scan as one pallas_call
# ---------------------------------------------------------------------------

def wave_solve(req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
               mask, static_scores, fit_col_w, bal_col_mask, shape_u,
               shape_s, w_fit, w_bal, strategy: str, wave_w: int,
               rows, exc, *, poison: bool, interpret: bool, perms=None):
    """Run the full wavefront solve as one fused pallas_call.

    Argument contract matches `solver._rescoring_wave_scan` (class
    planes addressed through `rows`, sparse exception column `exc`),
    plus `perms`: None runs the single identity order (K=1, the
    `greedy_assign_rescoring_wave` shape, with the exact in-step serial
    replay when `poison=False`); a (K,P) permutation batch runs all K
    orders in the SAME pallas_call — the grid's major axis — each with
    its own carry block (the vmapped-multistart shape, `poison=True`:
    speculation always commits and the first conflict poisons order k).

    Returns (assign (K, P) int32 in PERMUTED pod coordinates,
    commits (K,), replays (K,), poisoned (K,) bool) — the caller
    un-permutes and selects, exactly like the scan wrappers.
    """
    n = free_q.shape[0]
    p = req_q.shape[0]
    r = req_q.shape[1]
    W = max(1, min(wave_w, p))
    ex = jnp.full((p,), -1, jnp.int32) if exc is None else exc
    if perms is None:
        perm_ix = jnp.arange(p, dtype=jnp.int32)[None]
    else:
        perm_ix = perms
    K = perm_ix.shape[0]

    # Per-order pod streams, padded and reshaped to waves exactly like
    # solver._wave_split (zero padding; the real mask gates the rest).
    req_k = req_q[perm_ix]                                 # (K,P,R)
    rnz_k = req_nz_q[perm_ix]
    row_k = rows[perm_ix]
    ex_k = ex[perm_ix]
    pad = (-p) % W
    if pad:
        req_k = jnp.concatenate(
            [req_k, jnp.zeros((K, pad, r), req_k.dtype)], axis=1)
        rnz_k = jnp.concatenate(
            [rnz_k, jnp.zeros((K, pad, r), rnz_k.dtype)], axis=1)
        row_k = jnp.concatenate(
            [row_k, jnp.zeros((K, pad), row_k.dtype)], axis=1)
        ex_k = jnp.concatenate(
            [ex_k, jnp.zeros((K, pad), ex_k.dtype)], axis=1)
    nw = (p + pad) // W
    req_w = req_k.reshape(K, nw, W, r)
    rnz_w = rnz_k.reshape(K, nw, W, r)
    row_w = row_k.reshape(K, nw, W)
    ex_w = ex_k.reshape(K, nw, W)
    real_w = (jnp.arange(p + pad, dtype=jnp.int32) < p).reshape(nw, W)

    # The kernel receives the mask PACKED and unpacks in-step — the
    # fused form of the backend's bit-plane unpack stage. pack/unpack
    # of a bool plane is exact, so bit-identity is unaffected.
    bits = jnp.packbits(mask, axis=1)                      # (C, ceil(N/8))

    def _wave_step_kernel(req_ref, rnz_ref, row_ref, ex_ref, real_ref,
                          bits_ref, sc_ref, alloc_ref, fq0_ref, fp0_ref,
                          unz0_ref, colw_ref, balm_ref, su_ref, ss_ref,
                          wf_ref, wb_ref,
                          out_ref, stat_ref, cq_ref, cp_ref, cu_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _seed():
            # Fresh carry per order k: the chunk state enters once and
            # stays resident in the kernel's output blocks thereafter.
            cq_ref[...] = fq0_ref[...][None]
            cp_ref[...] = fp0_ref[...][None]
            cu_ref[...] = unz0_ref[...][None]
            stat_ref[...] = jnp.zeros_like(stat_ref)

        free_q = cq_ref[0]
        free_pods = cp_ref[0]
        used_nz = cu_ref[0]
        ncom = stat_ref[0, 0]
        nrep = stat_ref[0, 1]
        pois = stat_ref[0, 2]

        req = req_ref[0, 0]                                # (W,R)
        req_nz = rnz_ref[0, 0]
        row = row_ref[0, 0]                                # (W,)
        e = ex_ref[0, 0]
        real = real_ref[0]
        alloc_q = alloc_ref[...]
        static_scores = sc_ref[...]
        fit_col_w = colw_ref[...]
        bal_col_mask = balm_ref[...]
        shape_u = su_ref[...]
        shape_s = ss_ref[...]
        w_fit = wf_ref[0]
        w_bal = wb_ref[0]

        # Bit-mask unpack (big-endian, the backend's shift order).
        # A negative-step arange materializes as a captured constant,
        # which pallas kernels forbid — build the 7..0 shifts from iota.
        shifts = (7 - lax.broadcasted_iota(jnp.int32, (8,), 0)) \
            .astype(jnp.uint8)
        packed = bits_ref[...]
        mask = ((packed[:, :, None] >> shifts) & 1).reshape(
            packed.shape[0], -1).astype(jnp.bool_)[:, :n]

        # --- identical op sequence to solver's wave_step -------------
        iota_n = jnp.arange(n, dtype=jnp.int32)
        m = mask[row]
        m = m & ((e < 0)[:, None] | (iota_n[None, :] == e[:, None]))
        m = m & real[:, None]
        fits = m & jnp.all(req[:, None, :] <= free_q[None, :, :],
                           axis=-1) & (free_pods >= 1)[None, :]
        sc = static_scores[row]
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz, fit_col_w, strategy, shape_u,
            shape_s)
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz, bal_col_mask)
        masked = jnp.where(fits, sc, NEG_INF)
        node_of = jnp.broadcast_to(iota_n[None, :], masked.shape)
        b, y = solver._wave_spec_picks(masked, node_of, n, W)
        safe = jnp.minimum(y, n - 1)
        conflict = solver._wave_conflicts(
            b, y, n, req, req_nz, free_q, free_pods, used_nz, alloc_q,
            m[:, safe], static_scores[row[:, None], safe[None, :]],
            fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
            strategy)
        nreal = jnp.sum(real.astype(jnp.int32))

        def fast(st):
            fq, fp, unz, nc, nr, po = st
            hit = y < n
            fq = fq.at[safe].add(
                jnp.where(hit[:, None], -req, 0).astype(fq.dtype))
            fp = fp.at[safe].add(jnp.where(hit, -1, 0).astype(fp.dtype))
            unz = unz.at[safe].add(
                jnp.where(hit[:, None], req_nz, 0).astype(unz.dtype))
            return (fq, fp, unz, nc + nreal, nr, po), \
                jnp.where(hit, y, jnp.int32(-1))

        if poison:
            (fq, fp, unz, nc, nr, po), out = fast(
                (free_q, free_pods, used_nz, ncom, nrep,
                 pois | jnp.any(conflict).astype(jnp.int32)))
        else:
            def slow(st):
                fq, fp, unz, nc, nr, po = st

                def body(w, s):
                    fq, fp, unz, out = s
                    rq, rnz = req[w], req_nz[w]
                    fits_w = m[w] & jnp.all(rq[None, :] <= fq, axis=1) \
                        & (fp >= 1)
                    scw = static_scores[row[w]]
                    scw = scw + w_fit * kernels.fit_score(
                        alloc_q, unz, rnz[None, :], fit_col_w, strategy,
                        shape_u, shape_s)[0]
                    scw = scw + w_bal * kernels.balanced_allocation_score(
                        alloc_q, unz, rnz[None, :], bal_col_mask)[0]
                    mk = jnp.where(fits_w, scw, NEG_INF)
                    idx = jnp.argmax(mk).astype(jnp.int32)
                    idx = jnp.where(jnp.any(fits_w), idx, jnp.int32(-1))
                    hitw = idx >= 0
                    sf = jnp.clip(idx, 0, n - 1)
                    fq = fq.at[sf].add(
                        jnp.where(hitw, -rq, 0).astype(fq.dtype))
                    fp = fp.at[sf].add(
                        jnp.where(hitw, -1, 0).astype(fp.dtype))
                    unz = unz.at[sf].add(
                        jnp.where(hitw, rnz, 0).astype(unz.dtype))
                    return (fq, fp, unz, out.at[w].set(idx))

                fq2, fp2, unz2, out = lax.fori_loop(
                    0, W, body,
                    (fq, fp, unz, jnp.full((W,), -1, jnp.int32)))
                return (fq2, fp2, unz2, nc, nr + nreal, po), out

            (fq, fp, unz, nc, nr, po), out = lax.cond(
                jnp.any(conflict), slow, fast,
                (free_q, free_pods, used_nz, ncom, nrep, pois))

        cq_ref[0] = fq
        cp_ref[0] = fp
        cu_ref[0] = unz
        stat_ref[0] = jnp.stack([nc, nr, po, jnp.int32(0)])
        out_ref[0, 0] = out

    nb = bits.shape[1]
    c = bits.shape[0]
    su = jnp.asarray(shape_u)
    ss = jnp.asarray(shape_s)
    wf = jnp.asarray(w_fit, jnp.float32).reshape(1)
    wb = jnp.asarray(w_bal, jnp.float32).reshape(1)

    def _full(shape):
        return pl.BlockSpec(shape, lambda k, i: (0,) * len(shape))

    assign, stats, _, _, _ = pl.pallas_call(
        _wave_step_kernel,
        grid=(K, nw),
        in_specs=[
            pl.BlockSpec((1, 1, W, r), lambda k, i: (k, i, 0, 0)),
            pl.BlockSpec((1, 1, W, r), lambda k, i: (k, i, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda k, i: (k, i, 0)),
            pl.BlockSpec((1, 1, W), lambda k, i: (k, i, 0)),
            pl.BlockSpec((1, W), lambda k, i: (i, 0)),
            _full((c, nb)),
            _full(static_scores.shape),
            _full(alloc_q.shape),
            _full(free_q.shape),
            _full(free_pods.shape),
            _full(used_nz_q.shape),
            _full(fit_col_w.shape),
            _full(bal_col_mask.shape),
            _full(su.shape),
            _full(ss.shape),
            _full((1,)),
            _full((1,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, W), lambda k, i: (k, i, 0)),
            pl.BlockSpec((1, 4), lambda k, i: (k, 0)),
            pl.BlockSpec((1, n, r), lambda k, i: (k, 0, 0)),
            pl.BlockSpec((1, n), lambda k, i: (k, 0)),
            pl.BlockSpec((1, n, r), lambda k, i: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, nw, W), jnp.int32),
            jax.ShapeDtypeStruct((K, 4), jnp.int32),
            jax.ShapeDtypeStruct((K, n, r), free_q.dtype),
            jax.ShapeDtypeStruct((K, n), free_pods.dtype),
            jax.ShapeDtypeStruct((K, n, r), used_nz_q.dtype),
        ],
        interpret=interpret,
    )(req_w, rnz_w, row_w, ex_w, real_w, bits, static_scores, alloc_q,
      free_q, free_pods, used_nz_q, fit_col_w, bal_col_mask, su, ss,
      wf, wb)

    return (assign.reshape(K, -1)[:, :p], stats[:, 0], stats[:, 1],
            stats[:, 2] > 0)


# ---------------------------------------------------------------------------
# shard-local wave evaluation: the (W, local_n) stage of the sharded
# wavefront solve as one fused kernel under shard_map. The W pmax/pmin
# ICI reduction rounds, the global-coordinate conflict OR-reduce, and
# the commit/replay cond stay in the shard_map body unchanged (SURVEY
# §5.8) — only the per-wave plane gather/gate/score/mask fuses.
# ---------------------------------------------------------------------------

def wave_eval(mask, static_sc, alloc_q, free_q, free_pods, used_nz,
              req, req_nz, row, e, el, real, fit_col_w, bal_col_mask,
              shape_u, shape_s, w_fit, w_bal, strategy: str,
              *, interpret: bool):
    """Fused shard-local (W, local_n) wave evaluation.

    Returns (masked (W, local_n) scores with NEG_INF = infeasible,
    m (W, local_n) gated static mask) — the exact pair the sharded
    `wave_step` computes inline; `el` is the exception column in LOCAL
    shard coordinates (e - base), `e` the global one (for the -1 gate).
    """
    local_n = free_q.shape[0]
    sc_dtype = jnp.result_type(static_sc.dtype, jnp.float32)

    def _wave_eval_kernel(mask_ref, sc_ref, alloc_ref, fq_ref, fp_ref,
                          unz_ref, req_ref, rnz_ref, row_ref, e_ref,
                          el_ref, real_ref, colw_ref, balm_ref, su_ref,
                          ss_ref, wf_ref, wb_ref, masked_ref, m_ref):
        iota = jnp.arange(local_n, dtype=jnp.int32)
        req = req_ref[...]
        req_nz = rnz_ref[...]
        row = row_ref[...]
        e = e_ref[...]
        el = el_ref[...]
        real = real_ref[...]
        free_q = fq_ref[...]
        free_pods = fp_ref[...]
        used_nz = unz_ref[...]
        alloc_q = alloc_ref[...]
        w_fit = wf_ref[0]
        w_bal = wb_ref[0]
        m = mask_ref[...][row] \
            & ((e < 0)[:, None] | (iota[None, :] == el[:, None])) \
            & real[:, None]
        fits = m & jnp.all(req[:, None, :] <= free_q[None, :, :],
                           axis=-1) & (free_pods >= 1)[None, :]
        sc = sc_ref[...][row]
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz, colw_ref[...], strategy,
            su_ref[...], ss_ref[...])
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz, balm_ref[...])
        masked_ref[...] = jnp.where(fits, sc, NEG_INF).astype(sc_dtype)
        m_ref[...] = m

    W = req.shape[0]
    wf = jnp.asarray(w_fit, jnp.float32).reshape(1)
    wb = jnp.asarray(w_bal, jnp.float32).reshape(1)
    masked, m = pl.pallas_call(
        _wave_eval_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((W, local_n), sc_dtype),
            jax.ShapeDtypeStruct((W, local_n), jnp.bool_),
        ],
        interpret=interpret,
    )(mask, static_sc, alloc_q, free_q, free_pods, used_nz, req, req_nz,
      row, e, el, real, fit_col_w, bal_col_mask, jnp.asarray(shape_u),
      jnp.asarray(shape_s), wf, wb)
    return masked, m
