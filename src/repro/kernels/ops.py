"""Jit'd public wrappers around the Pallas kernels: shape normalization
(padding to lane/tile alignment), layout transposes, interpret-mode
dispatch, and per-shard execution under a tensor-parallel mesh.

Interpret mode is derived, never set: a kernel compiles with Mosaic when
the op is traced for a TPU backend and runs in the Pallas interpreter
everywhere else (``_interpret``).

A compiled Mosaic kernel is a custom call that the SPMD partitioner
cannot split.  When the op is traced under a mesh with a "model" axis
(a tensor-parallel serve replica sets one, see ``serve.Engine``), each
wrapper runs its kernel inside a ``shard_map`` over the mesh's
non-manual axes (``_per_shard``): operands are split on the axis the
kernel already treats independently (attention heads / kv heads,
recurrent-state channels) and replicated otherwise (block tables,
positions, MLA latent pools, frontier logits)."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import decode_view as _dv
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import fused_update as _fu
from repro.kernels import mla_decode as _mla
from repro.kernels import sampling as _sp
from repro.kernels import slot_state as _ss

_TP = "model"


def _interpret() -> bool:
    """Mosaic on a TPU backend, the Pallas interpreter on any other."""
    return jax.default_backend() != "tpu"


def _tp_width() -> int:
    """Size of the context mesh's non-manual "model" axis (1 if none)."""
    mesh = jax.sharding.get_abstract_mesh()
    if _TP not in mesh.axis_names or _TP in mesh.manual_axes:
        return 1
    return mesh.shape[_TP]


def _per_shard(fn, args, in_specs, out_specs):
    """``fn(*args)``, run per device when a mesh is in context.

    Every non-manual axis of the context mesh becomes manual: specs may
    name only "model"; the other axes see replicated operands.  With no
    mesh (single device) this is a plain call."""
    mesh = jax.sharding.get_abstract_mesh()
    free = set(mesh.axis_names) - set(mesh.manual_axes)
    if not free:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=free,
                         check_vma=False)(*args)


def _split(ndim: int, axis: int, n: int) -> P:
    """Spec sharding ``axis`` of a rank-``ndim`` operand over "model"
    when the TP width divides ``n``, else replicated."""
    tp = _tp_width()
    if tp == 1 or n % tp:
        return P()
    spec = [None] * ndim
    spec[axis] = _TP
    return P(*spec)


def _head_specs(q_ndim: int, q_axis: int, heads: int, kv_ndim: int,
                kv_axis: int, kv_heads: int) -> Tuple[P, P]:
    """Query-head and kv-head specs that keep every GQA group on one
    shard: both split when the TP width divides both head counts, else
    both replicated."""
    q, kv = _split(q_ndim, q_axis, heads), _split(kv_ndim, kv_axis, kv_heads)
    if q == P() or kv == P():
        return P(), P()
    return q, kv


# ---------------------------------------------------------------------------
# fused optimizer update
# ---------------------------------------------------------------------------


def fused_sgd_update(w, m, g, *, lr, momentum: float, weight_decay: float,
                     nesterov: bool = False, trust=None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Arbitrary-shape fused update; pads/reshapes to (R, 128) tiles."""
    shape = w.shape
    n = w.size
    lane = _fu.LANE
    tile = lane * _fu.BLOCK_ROWS
    pad = (-n) % tile

    def flat(x, dtype):
        x = x.reshape(-1).astype(dtype)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(-1, lane)

    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(1.0 if trust is None else trust,
                                  jnp.float32)]).reshape(1, 2)

    def run(w, m, g, scal):
        w2, m2 = _fu.fused_sgd_update_2d(
            flat(w, w.dtype), flat(m, m.dtype), flat(g, jnp.float32), scal,
            momentum=momentum, weight_decay=weight_decay, nesterov=nesterov,
            interpret=_interpret())
        return (w2.reshape(-1)[:n].reshape(shape),
                m2.reshape(-1)[:n].reshape(shape).astype(m.dtype))

    return _per_shard(run, (w, m, g, scal), (P(),) * 4, (P(), P()))


# ---------------------------------------------------------------------------
# flash attention (prefill/train fwd)
# ---------------------------------------------------------------------------


def _pad_heads(x, hd_pad):
    if hd_pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, hd_pad)])
    return x


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128) -> jax.Array:
    """q (B,S,H,hd); k,v (B,S,KV,hd) -> (B,S,H,hd).

    Pads hd to a 128 multiple and S to block multiples (padded kv masked
    via in-kernel seq_len guard; padded q rows discarded)."""
    sq, hd = q.shape[1], q.shape[3]
    sk = k.shape[1]
    hd_pad = (-hd) % 128
    sq_pad = (-sq) % block_q
    sk_pad = (-sk) % block_kv

    def run(q, k, v):
        qt = jnp.moveaxis(_pad_heads(q, hd_pad), 2, 1)     # (B,H,S,hd')
        kt = jnp.moveaxis(_pad_heads(k, hd_pad), 2, 1)
        vt = jnp.moveaxis(_pad_heads(v, hd_pad), 2, 1)
        if sq_pad:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
        if sk_pad:
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
        if hd_pad:
            # keep softmax scale consistent with true hd
            qt = qt * ((hd + hd_pad) ** 0.5 / hd ** 0.5)
        o = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal,
                                     window=window, block_q=block_q,
                                     block_kv=block_kv, valid_len=sk,
                                     interpret=_interpret())
        return jnp.moveaxis(o[:, :, :sq, :hd], 1, 2)

    qs, ks = _head_specs(4, 2, q.shape[2], 4, 2, k.shape[2])
    return _per_shard(run, (q, k, v), (qs, ks, ks), qs)


# ---------------------------------------------------------------------------
# flash decode (one token vs KV cache)
# ---------------------------------------------------------------------------


def flash_decode(q, k, v, length, *, block_kv: int = 512) -> jax.Array:
    """q (B,H,hd); k,v (B,S,KV,hd); length = #valid slots -> (B,H,hd)."""
    hd = q.shape[2]
    s = k.shape[1]
    hd_pad = (-hd) % 128
    s_pad = (-s) % block_kv

    def run(q, k, v, length):
        qp = _pad_heads(q, hd_pad)
        kp = _pad_heads(k, hd_pad)
        vp = _pad_heads(v, hd_pad)
        if s_pad:
            kp = jnp.pad(kp, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
            vp = jnp.pad(vp, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        if hd_pad:
            qp = qp * ((hd + hd_pad) ** 0.5 / hd ** 0.5)
        o = _fd.flash_decode_bhd(qp, kp, vp, length, block_kv=block_kv,
                                 interpret=_interpret())
        return o[..., :hd]

    qs, ks = _head_specs(3, 1, q.shape[1], 4, 2, k.shape[2])
    return _per_shard(run, (q, k, v, jnp.asarray(length, jnp.int32)),
                      (qs, ks, ks, P()), qs)


def flash_decode_paged(q, k_pool, v_pool, block_tables, pos, *,
                       window: int = 0) -> jax.Array:
    """Paged decode/prefill-chunk attention for repro.serve:
    q (B,C,H,hd) — C query tokens per row; pools (nb, bs, KV, hd);
    block_tables (B,NB); pos (B,) absolute position of each row's first
    query -> (B,C,H,hd).  The kernel's work follows the call's query
    runs and their live pages (``flash_decode.query_tiles``, computed
    per shard from the replicated tables and positions).

    When hd % 128 != 0 this pads the ENTIRE pools on every call —
    O(pool) per layer per step.  A production TPU caller should
    allocate its pools at a 128-aligned head_dim and hit the zero-pad
    fast path here."""
    hd = q.shape[3]
    hd_pad = (-hd) % 128

    def run(q, k_pool, v_pool, block_tables, pos):
        qp = _pad_heads(q, hd_pad)
        kp = _pad_heads(k_pool, hd_pad)
        vp = _pad_heads(v_pool, hd_pad)
        if hd_pad:
            qp = qp * ((hd + hd_pad) ** 0.5 / hd ** 0.5)
        o = _fd.flash_decode_paged_bhd(qp, kp, vp, block_tables, pos,
                                       window=window,
                                       interpret=_interpret())
        return o[..., :hd]

    qs, ks = _head_specs(4, 2, q.shape[2], 4, 2, k_pool.shape[2])
    return _per_shard(run, (q, k_pool, v_pool, block_tables, pos),
                      (qs, ks, ks, P(), P()), qs)


def decode_view_attend(q, k_view, v_view, pos, *, window: int = 0,
                       block_kv: int = 128) -> jax.Array:
    """Decode attention over the N-step loop's per-row contiguous views:
    q (B,H,hd); k_view,v_view (B,S,KV,hd) with slot j = logical position
    j (the trailing trash slot and unwritten frontier slots are masked
    in-kernel by ``kpos <= pos``); pos (B,) -> (B,H,hd).

    Replaces the jnp gather+softmax of attention.paged_decode_attention
    inside the fori_loop.  Pads hd to 128 lanes and S to the kv-block
    multiple; ``scale`` is passed into the kernel from the TRUE head
    dim, so padding never perturbs the softmax.  Padded kv slots carry
    kpos >= S and every live row's pos is < S, so they mask out."""
    hd = q.shape[2]
    s = k_view.shape[1]
    hd_pad = (-hd) % 128
    bk = min(block_kv, -(-s // 128) * 128)
    s_pad = (-s) % bk

    def run(q, k_view, v_view, pos):
        qp = _pad_heads(q, hd_pad)
        kp = _pad_heads(k_view, hd_pad)
        vp = _pad_heads(v_view, hd_pad)
        if s_pad:
            kp = jnp.pad(kp, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
            vp = jnp.pad(vp, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        o = _dv.decode_view_attend_bhd(qp, kp, vp, pos,
                                       scale=1.0 / (hd ** 0.5),
                                       window=window, block_kv=bk,
                                       interpret=_interpret())
        return o[..., :hd]

    qs, ks = _head_specs(3, 1, q.shape[1], 4, 2, k_view.shape[2])
    return _per_shard(run, (q, k_view, v_view, pos), (qs, ks, ks, P()), qs)


# ---------------------------------------------------------------------------
# MLA absorbed-query latent attends (views + paged pools)
# ---------------------------------------------------------------------------


def _pad_lanes(x, pad):
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


def mla_decode_views(q_lat, q_rope, ckv, kr, pos, *, scale,
                     block: int = 128) -> jax.Array:
    """Pallas form of ref.mla_decode_views: q_lat (B,C,H,r), q_rope
    (B,C,H,rd); ckv (B,S,r), kr (B,S,rd) per-row contiguous latent
    views; pos (B,) -> o_lat (B,C,H,r).  Pads r/rd to 128 lanes and S
    to the block multiple — zero pads are inert because ``scale`` is
    explicit and padded kpos always exceeds live positions.  Under TP
    the heads split and the latent stream is replicated."""
    r, rd = q_lat.shape[-1], q_rope.shape[-1]
    s = ckv.shape[1]
    r_pad, rd_pad = (-r) % 128, (-rd) % 128
    bk = min(block, -(-s // 128) * 128)
    s_pad = (-s) % bk

    def run(q_lat, q_rope, ckv, kr, pos):
        qlp, ckvp = _pad_lanes(q_lat, r_pad), _pad_lanes(ckv, r_pad)
        qrp, krp = _pad_lanes(q_rope, rd_pad), _pad_lanes(kr, rd_pad)
        if s_pad:
            ckvp = jnp.pad(ckvp, ((0, 0), (0, s_pad), (0, 0)))
            krp = jnp.pad(krp, ((0, 0), (0, s_pad), (0, 0)))
        o = _mla.mla_views_attend(qlp, qrp, ckvp, krp, pos, scale=scale,
                                  block=bk, interpret=_interpret())
        return o[..., :r]

    qs = _split(4, 2, q_lat.shape[2])
    return _per_shard(run, (q_lat, q_rope, ckv, kr, pos),
                      (qs, qs, P(), P(), P()), qs)


def mla_decode_paged(q_lat, q_rope, ckv_pool, kr_pool, block_tables, pos,
                     *, scale) -> jax.Array:
    """Pallas form of ref.mla_decode_paged: the block table rides in
    scalar prefetch and routes each latent block's DMA — no gathered
    (B, S, r) intermediate at all.  Pools (nb,bs,r)/(nb,bs,rd);
    q_lat (B,C,H,r); block_tables (B,NB); pos (B,) -> (B,C,H,r).

    When r/rd aren't 128-aligned the whole pools are zero-padded per
    call (same O(pool) caveat as flash_decode_paged — size production
    pools lane-aligned)."""
    r, rd = q_lat.shape[-1], q_rope.shape[-1]
    r_pad, rd_pad = (-r) % 128, (-rd) % 128

    def run(q_lat, q_rope, ckv_pool, kr_pool, block_tables, pos):
        o = _mla.mla_paged_attend(
            _pad_lanes(q_lat, r_pad), _pad_lanes(q_rope, rd_pad),
            _pad_lanes(ckv_pool, r_pad), _pad_lanes(kr_pool, rd_pad),
            block_tables, pos, scale=scale, interpret=_interpret())
        return o[..., :r]

    qs = _split(4, 2, q_lat.shape[2])
    return _per_shard(run, (q_lat, q_rope, ckv_pool, kr_pool, block_tables,
                            pos), (qs, qs, P(), P(), P(), P()), qs)


# ---------------------------------------------------------------------------
# slot-state gather/scatter (ssm/rglru recurrent pools)
# ---------------------------------------------------------------------------


def _state_spec(pool) -> P:
    """TP spec of a (S, *F) recurrent-state pool, mirroring
    ``sharding.serve_cache_pspecs``: the head axis of a 4-D SSD state
    pool, else the trailing channel axis."""
    axis = 1 if pool.ndim == 4 else pool.ndim - 1
    if pool.ndim < 2:
        return P()
    return _split(pool.ndim, axis, pool.shape[axis])


def slot_gather(pool, slots, fresh=None) -> jax.Array:
    """Gather per-sequence recurrent state rows: pool (S, *F);
    slots (B,); fresh (B,) bool — True rows (first token, no state yet)
    emit zeros.  Returns (B, *F) in pool dtype.  One routed DMA per
    row via scalar-prefetched slot indices; feature dims are flattened
    and lane-padded."""
    b = slots.shape[0]
    fr = (jnp.zeros((b,), jnp.int32) if fresh is None
          else jnp.asarray(fresh).astype(jnp.int32))

    def run(pool, slots, fr):
        s, feat = pool.shape[0], pool.shape[1:]
        f = math.prod(feat) if feat else 1
        p2 = _pad_lanes(pool.reshape(s, f), (-f) % 128)
        out = _ss.slot_gather_rows(p2, slots, fr, interpret=_interpret())
        return out[:, :f].reshape((b,) + feat)

    spec = _state_spec(pool)
    return _per_shard(run, (pool, jnp.asarray(slots, jnp.int32), fr),
                      (spec, P(), P()), spec)


def slot_scatter(pool, state_slots, valid_len, value) -> jax.Array:
    """Scatter per-sequence recurrent state back into the pool — the
    Pallas form of layers.slot_state_scatter (rows with valid_len == 0
    route to trash slot 0; valid_len=None writes unconditionally).
    pool (S, *F); state_slots (B,); value (B, *F).  Returns the updated
    pool.  The kernel walks pool rows against a host-built inverse map,
    so no in-place aliasing is needed."""
    slots = jnp.asarray(state_slots, jnp.int32)
    if valid_len is not None:
        slots = jnp.where(jnp.asarray(valid_len) > 0, slots, 0)

    def run(pool, slots, value):
        s, feat = pool.shape[0], pool.shape[1:]
        f = math.prod(feat) if feat else 1
        b = slots.shape[0]
        p2 = _pad_lanes(pool.reshape(s, f), (-f) % 128)
        v2 = _pad_lanes(value.astype(pool.dtype).reshape(b, f), (-f) % 128)
        out = _ss.slot_scatter_rows(p2, slots, v2, interpret=_interpret())
        return out[:, :f].reshape(pool.shape)

    spec = _state_spec(pool)
    return _per_shard(run, (pool, slots, value), (spec, P(), spec), spec)


# ---------------------------------------------------------------------------
# device-side serving sampler (greedy / temperature / top-k)
# ---------------------------------------------------------------------------


def sample_tokens(logits, keys, *, temperature: float, top_k: int = 0,
                  impl: str = "jnp"):
    """Per-row token sampling on device for the serving engine's fused
    step and N-step decode loop: greedy argmax at temperature <= 0,
    else top-k-restricted temperature categorical keyed per row
    (``ref.sample_keys``: fold_in(request, position) — stateless, so the
    draw is identical at every dispatch depth).

    impl="pallas" runs the fused streaming kernels (sampling.py):
    token-identical to the jnp oracle, including argmax ties and the
    gumbel draw (categorical IS gumbel-max; the noise comes from the
    same per-row keys, generated outside the kernel and streamed in).
    Under TP the frontier logits are replicated, so every shard samples
    the same tokens.
    """
    from repro.kernels import ref as _ref
    if impl != "pallas":
        return _ref.sample_tokens(logits, keys, temperature=temperature,
                                  top_k=top_k)
    if temperature <= 0.0:
        return _per_shard(
            lambda lg: _sp.greedy_sample(lg, interpret=_interpret()),
            (logits,), (P(),), P())
    v = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    g = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
    return _per_shard(
        lambda lg, g: _sp.gumbel_sample(lg, g, temperature=temperature,
                                        top_k=top_k,
                                        interpret=_interpret()),
        (lg, g), (P(), P()), P())


# ---------------------------------------------------------------------------
# SSD intra-chunk (Mamba-2)
# ---------------------------------------------------------------------------


def ssd_chunk(x, dt, dacum, B, C):
    """x (bc,l,h,p); dt/dacum (bc,l,h); B,C (bc,l,h,n) ->
    (y (bc,l,h,p), states (bc,h,n,p)).  Pads p/n to 128 lanes; under TP
    the heads split."""
    from repro.kernels import ssd_chunk as _sc
    p, n = x.shape[-1], B.shape[-1]
    p_pad, n_pad = (-p) % 128, (-n) % 128
    hm = lambda a: jnp.swapaxes(a, 1, 2)            # (bc,l,h,.) <-> (bc,h,l,.)

    def run(x, dt, dacum, B, C):
        y, st = _sc.ssd_chunk_bhcp(
            hm(_pad_lanes(x, p_pad)), hm(dt), hm(dacum),
            hm(_pad_lanes(B, n_pad)), hm(_pad_lanes(C, n_pad)),
            interpret=_interpret())
        return hm(y)[..., :p], st[:, :, :n, :p]

    h = x.shape[2]
    s4, s3 = _split(4, 2, h), _split(3, 2, h)
    return _per_shard(run, (x, dt, dacum, B, C), (s4, s3, s3, s4, s4),
                      (s4, _split(4, 1, h)))
