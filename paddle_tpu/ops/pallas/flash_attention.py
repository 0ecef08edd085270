"""Flash attention as Pallas TPU kernels (fwd + blockwise bwd, custom_vjp).

Role in the framework: the training-time fused attention path.  The reference
has no training flash kernel (its fused attention, operators/fused/
multihead_matmul_op.cu, is inference-only and materializes the full score
matrix); this kernel is the TPU-native upgrade: O(L) memory via online
softmax, blocks sized to the MXU/VMEM, f32 accumulation over bf16 inputs.

Layout: q,k,v are [B, H, L, D], flattened to [B*H, L, D] for the kernels.
Grid iteration (TPU grids run sequentially, last axis innermost) carries the
online-softmax state (m, l, acc) in VMEM scratch across the K-block axis.

Supported in-kernel: causal masking and a key padding mask [B, Lk] (additive,
0/-inf semantics).  Full [B, H, Lq, Lk] masks fall back to the XLA composite
in ops/attention.py.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret():
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret):
    """The `interpret` flag a pallas_call gets: by backend when the
    caller passed None, the caller's choice otherwise — except that
    nothing may run the interpreter on a TPU, where it would pass for
    the kernel while Mosaic never ran."""
    if interpret is None:
        return _interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "interpret=True on the TPU backend: the Pallas interpreter "
            "would stand in for the Mosaic kernel; pass interpret=None")
    return bool(interpret)


_DEFAULT_BLOCK = 512  # swept on v5e: 512 beats 128 ~2x (fewer grid steps)


def _choose_block(n):
    # Tile-legal by construction: a 128-multiple block, or one block spanning
    # the whole axis (a block equal to the array dim is always legal, even
    # when the dim is not an (8,128) multiple — Mosaic pads it).  Reads
    # _DEFAULT_BLOCK at call time so tests/benches can override it.
    for b in (_DEFAULT_BLOCK, 256, 128):
        if b <= _DEFAULT_BLOCK and n % b == 0:
            return b
    return n


def _check_mosaic_specs(specs, shapes, where):
    """Static Mosaic tiling check, run on EVERY backend (so interpret-mode
    CPU tests cannot mask a violation the real TPU lowering would reject).

    Rule (f32-class dtypes): for rank>=2 blocks, the last block dim must be
    a multiple of 128 or equal to the full array dim, and the second-to-last
    a multiple of 8 or equal to the full array dim.  This is the check that
    round-4's lse out_spec (1, block_q) over (bh, lq) failed on hardware.
    """
    for idx, (spec, shape) in enumerate(zip(specs, shapes)):
        blk = spec.block_shape
        if blk is None or len(blk) < 2:
            continue
        ok_last = blk[-1] % 128 == 0 or blk[-1] == shape[-1]
        ok_sub = blk[-2] % 8 == 0 or blk[-2] == shape[-2]
        if not (ok_last and ok_sub):
            raise ValueError(
                f"flash_attention {where}[{idx}]: block {tuple(blk)} over "
                f"array {tuple(shape)} violates Mosaic (8,128) tiling")


def _causal_mask(s, qb, kb, block_q, block_k, offset):
    # query row i may see key j iff j <= i + offset, offset = Lk - Lq —
    # matching the composite path's tril(k=Lk-Lq) (KV-cache decoding shape)
    rows = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows + offset >= cols, s, NEG_INF)


def _causal_block_runs(qb, kb, block_q, block_k, offset):
    # K-block overlaps the allowed region iff its first key index is <= the
    # last query row's limit
    return kb * block_k <= (qb + 1) * block_q - 1 + offset


# ------------------------------ forward ---------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, kmask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, causal, block_q, block_k,
                n_kb, have_mask, offset):
    # m/l scratch are (block_q, 128) with every lane holding the row value
    # (broadcast-write, max-read): full-width vector ops only, no strided
    # single-lane stores, matching the Mosaic-proven layout.
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a K-block strictly above the causal diagonal contributes nothing
    run = _causal_block_runs(qb, kb, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _compute():
        # matmuls run in the NATIVE input dtype with f32 accumulation: the
        # MXU takes bf16 operands at full rate, while pre-casting to f32
        # forces multi-pass f32 matmuls (~3x slower, measured on v5e)
        q = q_ref[0]                               # [block_q, d]
        k = k_ref[0]                               # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if have_mask:
            s = s + kmask_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _causal_mask(s, qb, kb, block_q, block_k, offset)

        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)   # [block_q, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)            # rescale of old state
        p = jnp.exp(s - m_cur)                     # [block_q, block_k]
        # fully-masked rows saturate at s == m_cur == NEG_INF, where exp(0)
        # would leak weight 1 per key; re-mask so l stays 0 for them
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = jnp.max(l_ref[...], axis=1, keepdims=True)        # [block_q, 1]
        # fully-masked rows (padding): emit zeros, lse -> NEG_INF
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        m_fin = jnp.max(m_ref[...], axis=1, keepdims=True)
        lse_ref[0] = jnp.where(l > 0.0, m_fin + jnp.log(safe_l), NEG_INF)


def _flash_fwd_call(qs, k, v, km, causal, heads, have_mask):
    # km is [Bm, 1, Lk] (Bm = batch or 1): the middle singleton keeps every
    # 2-D-per-row operand rank-3 so its (1, 1, block) BlockSpec is Mosaic
    # tile-legal regardless of the leading dim (round-4 TPU crash class).
    bh, lq, d = qs.shape
    _, lk, _ = k.shape
    block_q, block_k = _choose_block(lq), _choose_block(lk)
    n_qb, n_kb = lq // block_q, lk // block_k

    km_index = (lambda b, i, j: (b // heads, 0, j)) if have_mask else (
        lambda b, i, j: (0, 0, j))
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, 1, block_k), km_index),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, lq, d), qs.dtype),
        jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
    ]
    _check_mosaic_specs(in_specs, [a.shape for a in (qs, k, v, km)], "in")
    _check_mosaic_specs(out_specs, [s.shape for s in out_shape], "out")
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, n_kb=n_kb, have_mask=have_mask,
                          offset=lk - lq),
        grid=(bh, n_qb, n_kb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_interpret(),
    )(qs, k, v, km)
    return out, lse


# ------------------------------ backward --------------------------------


def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                     kmask_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                     causal, block_q, block_k, n_qb, have_mask, offset):
    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = _causal_block_runs(qb, kb, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _compute():
        # native-dtype matmul operands, f32 accumulation (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                           # [block_q, 1]
        delta = delta_ref[0]                       # [block_q, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if have_mask:
            s = s + kmask_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _causal_mask(s, qb, kb, block_q, block_k, offset)
        p = jnp.exp(s - lse)                       # [block_q, block_k]
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)    # see fwd kernel note
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   kmask_ref, dq_ref, dq_acc, *, causal, block_q, block_k,
                   n_kb, have_mask, offset):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = _causal_block_runs(qb, kb, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _compute():
        # native-dtype matmul operands, f32 accumulation (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                           # [block_q, 1]
        delta = delta_ref[0]                       # [block_q, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if have_mask:
            s = s + kmask_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _causal_mask(s, qb, kb, block_q, block_k, offset)
        p = jnp.exp(s - lse)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)    # see fwd kernel note
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_call(qs, k, v, km, out, lse, do, causal, heads, have_mask):
    # lse/delta ride as [bh, Lq, 1] columns and km as [Bm, 1, Lk] rows so
    # every BlockSpec satisfies Mosaic's (8, 128) tiling (see fwd call).
    bh, lq, d = qs.shape
    _, lk, _ = k.shape
    block_q, block_k = _choose_block(lq), _choose_block(lk)
    n_qb, n_kb = lq // block_q, lk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    km_idx_kq = (lambda b, j, i: (b // heads, 0, j)) if have_mask else (
        lambda b, j, i: (0, 0, j))
    in_specs_kq = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, 1, block_k), km_idx_kq),
    ]
    out_specs_kq = [
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
    ]
    operands = (qs, do, lse, delta, k, v, km)
    _check_mosaic_specs(in_specs_kq, [a.shape for a in operands], "bwd-in")
    _check_mosaic_specs(out_specs_kq, [k.shape, v.shape], "bwd-out")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, n_qb=n_qb, have_mask=have_mask,
                          offset=lk - lq),
        grid=(bh, n_kb, n_qb),
        in_specs=in_specs_kq,
        out_specs=out_specs_kq,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="flash_bwd_dkdv",
        interpret=_interpret(),
    )(*operands)

    km_idx_qk = (lambda b, i, j: (b // heads, 0, j)) if have_mask else (
        lambda b, i, j: (0, 0, j))
    in_specs_qk = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, 1, block_k), km_idx_qk),
    ]
    _check_mosaic_specs(in_specs_qk, [a.shape for a in operands], "bwd-in")
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, n_kb=n_kb, have_mask=have_mask,
                          offset=lk - lq),
        grid=(bh, n_qb, n_kb),
        in_specs=in_specs_qk,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qs.shape, qs.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(*operands)
    return dq, dk, dv


# --------------------------- custom_vjp glue ----------------------------
# km is always a materialized array (zeros placeholder when no mask) so the
# nondiff argnums stay hashable python values.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(qs, k, v, km, causal, heads, have_mask):
    out, _ = _flash_fwd_call(qs, k, v, km, causal, heads, have_mask)
    return out


def _flash_fwd_rule(qs, k, v, km, causal, heads, have_mask):
    out, lse = _flash_fwd_call(qs, k, v, km, causal, heads, have_mask)
    return out, (qs, k, v, km, out, lse)


def _flash_bwd_rule(causal, heads, have_mask, res, do):
    qs, k, v, km, out, lse = res
    dq, dk, dv = _flash_bwd_call(qs, k, v, km, out, lse, do, causal, heads,
                                 have_mask)
    return dq, dk, dv, jnp.zeros_like(km)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------- public entry -------------------------------


def flash_attention(q, k, v, attn_mask=None, causal=False):
    """q,k,v: Tensor or array [B, H, L, D].  attn_mask: None or an additive
    mask whose non-trivial axes are batch and key (shapes [B,1,1,Lk] /
    [B,Lk] / [1,1,1,Lk]); richer masks must use the XLA composite path
    (see mask_is_flash_compatible)."""
    from ...core.registry import apply_op

    def fn(qv, kv, vv, *mask):
        b, h, lq, dh = qv.shape
        lk = kv.shape[2]
        scale = 1.0 / math.sqrt(dh)
        # fold the scale into q: s = (q*scale) @ k^T everywhere, so the vjp
        # of the fold handles dq's scale automatically
        qs = (qv * scale).reshape(b * h, lq, dh)
        kf = kv.reshape(b * h, lk, dh)
        vf = vv.reshape(b * h, lk, dh)
        have_mask = bool(mask)
        if have_mask:
            m = mask[0]
            km = jnp.broadcast_to(
                m, (b,) + tuple(m.shape[1:])).reshape(b, -1)
            km = km[:, -lk:].astype(jnp.float32).reshape(b, 1, lk)
        else:
            km = jnp.zeros((1, 1, lk), jnp.float32)
        out = _flash(qs, kf, vf, km, causal, h, have_mask)
        return out.reshape(b, h, lq, dh)

    args = (q, k, v) + ((attn_mask,) if attn_mask is not None else ())
    return apply_op("flash_attention", fn, args, {})


def shapes_are_flash_compatible(lq, lk, d=None):
    """Shapes the kernel handles within VMEM: non-128-multiple axes run as
    one full-axis block, so bound what the kernel would actually resident —
    the f32 score block (block_q x block_k) plus, when the head dim is
    known, the d-dependent blocks: q/out/acc (block_q x d), k/v and the
    backward's dk/dv scratch (block_k x d), and the online-softmax state
    (block_q x 128 x 2), all f32 and doubled for Mosaic's input
    double-buffering.  The combined budget is half of a v5e core's ~16 MB
    VMEM; large-d shapes that blow it fall back to the composite path
    instead of over-allocating VMEM at compile time."""
    bq, bk = _choose_block(lq), _choose_block(lk)
    score = bq * bk * 4
    if d is None:
        # legacy seq-only bound: 4 MB leaves room for typical (d<=128)
        # q/k/v blocks and scratch
        return score <= 4 * 1024 * 1024
    d_blocks = 4 * (3 * bq * d + 4 * bk * d + 2 * bq * 128) * 2
    return score + d_blocks <= 8 * 1024 * 1024


def mask_is_flash_compatible(attn_mask):
    """True when the mask varies only along batch and key axes: None or
    4-D [B|1, 1, 1, Lk].  2-D masks are ambiguous under the sdp contract
    ([Lq, Lk] broadcast) — those take the composite path."""
    if attn_mask is None:
        return True
    shape = tuple(attn_mask.shape)
    return len(shape) == 4 and shape[1] == 1 and shape[2] == 1
