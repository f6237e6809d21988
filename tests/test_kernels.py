"""Pallas kernel sweeps: shapes x dtypes, assert_allclose against the
ref.py pure-jnp oracles (interpret mode on CPU; TPU is the target)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dt):
    return dict(atol=3e-2, rtol=3e-2) if dt == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (128,), (1000,), (257, 129),
                                   (8, 128, 3), (2048, 512)])
@pytest.mark.parametrize("wdt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_update_sweep(shape, wdt, nesterov):
    ks = jax.random.split(jax.random.key(sum(shape)), 3)
    w = jax.random.normal(ks[0], shape, jnp.float32).astype(wdt)
    m = jax.random.normal(ks[1], shape, jnp.float32)
    g = jax.random.normal(ks[2], shape, jnp.float32)
    kw = dict(lr=0.05, momentum=0.9, weight_decay=1e-4, nesterov=nesterov)
    w1, m1 = ops.fused_sgd_update(w, m, g, **kw)
    w2, m2 = ref.fused_sgd_update(w, m, g, **kw)
    assert w1.dtype == w.dtype and m1.dtype == m.dtype
    np.testing.assert_allclose(np.float32(w1), np.float32(w2), **_tol(wdt))
    np.testing.assert_allclose(np.float32(m1), np.float32(m2), atol=1e-5)


def test_fused_update_with_trust_ratio():
    shape = (300, 40)
    ks = jax.random.split(jax.random.key(0), 3)
    w = jax.random.normal(ks[0], shape)
    m = jnp.zeros(shape)
    g = jax.random.normal(ks[2], shape)
    kw = dict(lr=0.1, momentum=0.9, weight_decay=1e-4, trust=jnp.float32(0.37))
    w1, m1 = ops.fused_sgd_update(w, m, g, **kw)
    w2, m2 = ref.fused_sgd_update(w, m, g, **kw)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)


def test_fused_update_traced_lr_under_jit():
    shape = (512,)
    w = jnp.ones(shape)
    m = jnp.zeros(shape)
    g = jnp.ones(shape)

    @jax.jit
    def f(lr):
        return ops.fused_sgd_update(w, m, g, lr=lr, momentum=0.9,
                                    weight_decay=0.0)[0]

    np.testing.assert_allclose(np.asarray(f(0.5)), 0.5 * np.ones(shape),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

SHAPES = [
    # b, sq, sk, h, kv, hd, causal, window
    (2, 256, 256, 4, 2, 64, True, 0),
    (1, 128, 128, 8, 8, 128, True, 0),
    (2, 200, 200, 2, 1, 80, False, 0),     # unaligned: pads S and hd
    (1, 384, 384, 4, 2, 64, True, 128),    # sliding window
    (1, 64, 320, 2, 2, 32, False, 0),      # cross-shape (sq != sk)
]


@pytest.mark.parametrize("case", SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dt):
    b, sq, sk, h, kv, hd, causal, window = case
    ks = jax.random.split(jax.random.key(sq + sk + h), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (b, sk, kv, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (b, sk, kv, hd), jnp.float32).astype(dt)
    o1 = ops.flash_attention(q, k, v, causal=causal, window=window)
    o2 = jnp.moveaxis(
        ref.flash_attention_bhsd(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                 jnp.moveaxis(v, 2, 1), causal=causal,
                                 window=window), 1, 2)
    assert o1.shape == q.shape and o1.dtype == q.dtype
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


def test_flash_attention_matches_model_blocked_path():
    """Kernel vs the model's jnp blocked attention (the exec-path oracle)."""
    from repro.models import attention as mattn
    b, s, h, kv, hd = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    qg = q.reshape(b, s, kv, h // kv, hd)
    o_model = mattn.blocked_attention(qg, k, v, causal=True, block_q=64,
                                      block_kv=64).reshape(b, s, h, hd)
    o_kernel = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               atol=3e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

DECODE_SHAPES = [
    # b, s, h, kv, hd, length
    (2, 512, 8, 2, 64, 300),
    (1, 1024, 4, 4, 128, 1024),
    (3, 700, 2, 1, 96, 13),    # unaligned cache + tiny valid length
]


@pytest.mark.parametrize("case", DECODE_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(case, dt):
    b, s, h, kv, hd, length = case
    ks = jax.random.split(jax.random.key(s + h), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dt)
    o1 = ops.flash_decode(q, k, v, length)
    o2 = ref.flash_decode(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                          length)
    assert o1.shape == (b, h, hd)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


def test_flash_decode_equals_model_decode_attention():
    from repro.models import attention as mattn
    b, s, h, kv, hd, pos = 2, 256, 4, 2, 64, 100
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    qg = q[:, None].reshape(b, 1, kv, h // kv, hd)
    o_model = mattn.decode_attention(qg, k, v, jnp.int32(pos)
                                     ).reshape(b, h, hd)
    o_kernel = ops.flash_decode(q, k, v, pos + 1)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               atol=3e-5)


# ---------------------------------------------------------------------------
# paged flash decode (repro.serve block pools)
# ---------------------------------------------------------------------------

def _random_tables(rng, nb, b, nb_seq, c, bs):
    """Disjoint non-trash physical blocks per row, shuffled, and any
    position at which the row's c queries fit."""
    perm = rng.permutation(np.arange(1, nb))[:b * nb_seq]
    pos = rng.integers(0, nb_seq * bs - c + 1, (b,))
    return perm.reshape(b, nb_seq), pos


def _paged_layout(layout, rng, nb, bs, b, c, nb_seq, window):
    """(block tables, positions, physical blocks no query may read) of
    one sweep case.  Every layout but "random" is hand-built around the
    query runs the kernel cuts the call into."""
    bt, pos = _random_tables(rng, nb, b, nb_seq, c, bs)
    dead = []                   # blocks no query may read
    if layout == "mixed":
        # width-1 mixed step: 3 decode rows, then request A's prompt
        # tokens 5..11 and request B's 0..3, one row per token
        pos[:3] = [3, nb_seq * bs - 1, 17]
        bt[3:10], pos[3:10] = bt[3], np.arange(5, 12)
        bt[10:14], pos[10:14] = bt[10], np.arange(4)
    elif layout == "long_run":
        # one run of b - 2 tokens, longer than a query tile, crossing
        # pages; a decode row on each side
        bt[1:b - 1], pos[1:b - 1] = bt[1], np.arange(3, b + 1)
    elif layout == "same_table_gap":
        # identical tables, positions 5, 9, 10, 10: three runs
        bt[:4], pos[:4] = bt[0], [5, 9, 10, 10]
    elif layout == "padded":
        # padding rows: position 0, every entry the trash block
        bt[1::2], pos[1::2] = 0, 0
    elif layout == "full_table":
        pos[0] = nb_seq * bs - c
    elif layout == "window_trash":
        # pages wholly before the first query's window are reclaimed to
        # the trash block, which the kernel must not read
        pos[:] = nb_seq * bs - c - rng.integers(0, bs, (b,))
        for r in range(b):
            bt[r, :max(0, (int(pos[r]) - window + 1) // bs)] = 0
        dead = [0]
    elif layout == "chunk_rows":
        # chunk-wide rows: rows 0-2 are one request's consecutive chunks
        bt[:3], pos[:3] = bt[0], np.arange(3) * c + 2
    return jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32), dead


PAGED_SHAPES = [
    # nb, bs, kv, hd, b, c, h, nb_seq, window, layout
    (16, 8, 2, 64, 3, 1, 4, 4, 0, "random"),
    (9, 16, 1, 128, 2, 1, 4, 4, 0, "random"),
    (32, 8, 4, 96, 2, 1, 8, 6, 20, "random"),   # GQA + window + hd pad
    (16, 8, 2, 64, 3, 4, 4, 4, 0, "random"),    # chunked queries (prefill)
    (32, 8, 4, 96, 2, 8, 8, 6, 20, "random"),   # chunk + window + hd pad
    (64, 8, 2, 64, 16, 1, 4, 3, 0, "mixed"),    # decode rows, two prompts
    (2449, 8, 2, 64, 136, 1, 4, 18, 0, "long_run"),
    (20, 8, 2, 64, 5, 1, 4, 3, 0, "same_table_gap"),
    (20, 8, 2, 64, 6, 1, 4, 3, 0, "padded"),
    (12, 16, 2, 128, 3, 1, 4, 3, 0, "full_table"),
    (40, 8, 2, 64, 4, 2, 4, 8, 12, "window_trash"),
    (40, 8, 2, 64, 4, 8, 4, 8, 0, "chunk_rows"),
]


@pytest.mark.parametrize("case", PAGED_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_sweep(case, dt):
    nb, bs, kv, hd, b, c, h, nb_seq, window, layout = case
    ks = jax.random.split(jax.random.key(nb + hd + c), 3)
    q = jax.random.normal(ks[0], (b, c, h, hd), jnp.float32).astype(dt)
    kp = jax.random.normal(ks[1], (nb, bs, kv, hd), jnp.float32).astype(dt)
    vp = jax.random.normal(ks[2], (nb, bs, kv, hd), jnp.float32).astype(dt)
    bt, pos, dead = _paged_layout(layout, np.random.default_rng(nb), nb, bs,
                                  b, c, nb_seq, window)
    o2 = ref.flash_decode_paged(q, kp, vp, bt, pos, window=window)
    if dead:
        # blocks no query may read hold NaN: one read spoils the output
        dead = np.array(dead)
        kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
    o1 = ops.flash_decode_paged(q, kp, vp, bt, pos, window=window)
    assert o1.shape == (b, c, h, hd)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


@pytest.mark.parametrize("xp", [np, jnp])
@pytest.mark.parametrize("window", [0, 6])
def test_query_tiles_hand_built_mixed_layout(xp, window):
    """Runs, tiles and live pages of a width-1 mixed step (block 4, 8
    pages a table): decode rows at positions 20 and 3, one prompt's
    tokens 5-9 on one table, two padding rows."""
    from repro.kernels import flash_decode as fd
    ta, tb, tc = np.arange(1, 9), np.arange(9, 17), np.arange(17, 25)
    pad = np.zeros(8, int)
    bt = np.stack([ta, tb] + [tc] * 5 + [pad, pad])
    pos = np.array([20, 3, 5, 6, 7, 8, 9, 0, 0])
    qt = fd.query_tiles(bt, pos, 1, block_size=4, window=window, xp=xp)
    k = int(qt.n_tiles)
    assert (k, int(qt.n_runs)) == (5, 5)
    assert np.asarray(qt.start)[:k].tolist() == [0, 1, 2, 7, 8]
    assert np.asarray(qt.length)[:k].tolist() == [1, 1, 5, 1, 1]
    assert np.asarray(qt.hi)[:k].tolist() == [5, 0, 2, 0, 0]
    # with a window of 6, row 0 (position 20) first sees position 15
    lo = [3, 0, 0, 0, 0] if window else [0] * 5
    assert np.asarray(qt.lo)[:k].tolist() == lo
    assert int(qt.n_pages) == (9 if window else 12)
    if xp is jnp:               # padded to one tile per token, length 0
        assert np.asarray(qt.length)[k:].tolist() == [0] * (9 - k)


def test_query_tiles_split_a_run_longer_than_a_tile():
    """Three 64-token chunk rows of one request make one 192-token run;
    grid blocks of two rows cut it at the 128-token tile."""
    from repro.kernels import flash_decode as fd
    bt = np.tile(np.arange(1, 17), (3, 1))
    assert fd.tile_shape(3, 64) == (2, 128)
    qt = fd.query_tiles(bt, np.array([0, 64, 128]), 64, block_size=16)
    assert (int(qt.n_runs), int(qt.n_tiles)) == (1, 2)
    assert qt.start.tolist() == [0, 128] and qt.length.tolist() == [128, 64]
    assert qt.hi.tolist() == [7, 11] and int(qt.n_pages) == 8 + 12
    assert qt.block_first.tolist() == [0, 1, 2]


def test_query_tiles_cut_runs_at_block_edges():
    """Width-1 rows: 120 padding rows, then one request's 16 prompt
    tokens in rows 120-135, which the 128-row grid blocks cut in two; a
    100-token chunk row (a block of its own) tiles as 96 + 4 tokens."""
    from repro.kernels import flash_decode as fd
    bt = np.zeros((136, 8), int)
    bt[120:] = np.arange(1, 9)
    pos = np.zeros(136, int)
    pos[120:] = np.arange(16)
    assert fd.tile_shape(136, 1) == (128, 128)
    qt = fd.query_tiles(bt, pos, 1, block_size=4)
    k = int(qt.n_tiles)
    assert (k, int(qt.n_runs)) == (122, 121)
    assert qt.start[-2:].tolist() == [120, 128]
    assert qt.length[-2:].tolist() == [8, 8]
    assert qt.hi[-2:].tolist() == [1, 3] and int(qt.n_pages) == 120 + 2 + 4
    assert qt.block_first.tolist() == [0, 121, 122]
    assert fd.tile_shape(2, 100) == (1, 96)
    qt = fd.query_tiles(bt[:2], np.array([0, 100]), 100, block_size=16)
    assert (int(qt.n_runs), int(qt.n_tiles)) == (1, 4)
    assert qt.start.tolist() == [0, 96, 100, 196]
    assert qt.length.tolist() == [96, 4, 96, 4]


@pytest.mark.parametrize("rows,heads,kv,table,what", [
    (544, 112, 8, 256, "VMEM"),          # 128-token tiles of 112 heads
    (2304, 32, 8, 64, "scalar memory"),  # tables over the 1 MiB of SMEM
])
def test_flash_decode_paged_refuses_what_cannot_fit(rows, heads, kv, table,
                                                    what):
    """VMEM follows the tile and the heads, the block tables sit in
    scalar memory: a call past either is refused before it is traced."""
    from repro.kernels import flash_decode as fd
    bf = jnp.bfloat16
    args = (jax.ShapeDtypeStruct((rows, 1, heads, 128), bf),
            jax.ShapeDtypeStruct((4097, 16, kv, 128), bf),
            jax.ShapeDtypeStruct((4097, 16, kv, 128), bf),
            jax.ShapeDtypeStruct((rows, table), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    with pytest.raises(ValueError, match=what):
        jax.eval_shape(functools.partial(fd.flash_decode_paged_bhd,
                                         interpret=True), *args)
    assert fd.tile_shape(rows, 1) == (128, 128)


def test_flash_decode_paged_matches_contiguous():
    """A paged cache with the identity block table must agree with the
    contiguous flash decode kernel on the same tokens."""
    nb, bs, kv, hd, b, h = 9, 64, 2, 128, 2, 4
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    kp = jax.random.normal(ks[1], (nb, bs, kv, hd))
    vp = jax.random.normal(ks[2], (nb, bs, kv, hd))
    nb_seq = 4
    bt = jnp.stack([jnp.arange(1, 5), jnp.arange(5, 9)]).astype(jnp.int32)
    length = 200
    o_paged = ops.flash_decode_paged(q[:, None], kp, vp, bt,
                                     jnp.full((b,), length - 1))[:, 0]
    kc = kp[bt].reshape(b, nb_seq * bs, kv, hd)
    vc = vp[bt].reshape(b, nb_seq * bs, kv, hd)
    o_flat = ops.flash_decode(q, kc, vc, length, block_kv=64)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_flat),
                               atol=3e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged-MLA latent attention oracle (repro.serve latent block pools)
# ---------------------------------------------------------------------------

MLA_PAGED_SHAPES = [
    # nb, bs, r, rd, b, c, h, nb_seq
    (16, 8, 32, 16, 3, 1, 4, 4),
    (9, 16, 16, 8, 2, 4, 2, 3),     # chunked queries (fused prefill)
    (32, 8, 64, 32, 2, 8, 8, 6),
]


@pytest.mark.parametrize("case", MLA_PAGED_SHAPES)
def test_mla_decode_paged_oracle_vs_loop(case):
    """The vectorized paged-latent oracle must equal a per-row python
    loop computing masked absorbed attention over the gathered latents
    (an independently-written reference, not the same einsum chain)."""
    nb, bs, r, rd, b, c, h, nb_seq = case
    ks = jax.random.split(jax.random.key(sum(case)), 4)
    q_lat = jax.random.normal(ks[0], (b, c, h, r))
    q_rope = jax.random.normal(ks[1], (b, c, h, rd))
    ckv = jax.random.normal(ks[2], (nb, bs, r))
    kr = jax.random.normal(ks[3], (nb, bs, rd))
    rng = np.random.default_rng(nb)
    perm = rng.permutation(np.arange(1, nb))[:b * nb_seq]
    bt = jnp.asarray(perm.reshape(b, nb_seq), jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb_seq * bs - c + 1, (b,)), jnp.int32)
    scale = 1.0 / np.sqrt(r + rd)
    o = np.asarray(ref.mla_decode_paged(q_lat, q_rope, ckv, kr, bt, pos,
                                        scale=scale))
    ckv_n, kr_n = np.asarray(ckv), np.asarray(kr)
    ql_n, qr_n = np.asarray(q_lat), np.asarray(q_rope)
    for bi in range(b):
        lat = ckv_n[np.asarray(bt)[bi]].reshape(-1, r)      # (S, r)
        rope = kr_n[np.asarray(bt)[bi]].reshape(-1, rd)
        for ci in range(c):
            n_valid = int(pos[bi]) + ci + 1
            for hi in range(h):
                lg = (lat[:n_valid] @ ql_n[bi, ci, hi]
                      + rope[:n_valid] @ qr_n[bi, ci, hi]) * scale
                p = np.exp(lg - lg.max())
                p /= p.sum()
                want = p @ lat[:n_valid]
                np.testing.assert_allclose(o[bi, ci, hi], want,
                                           atol=2e-5, rtol=2e-5)


def test_mla_paged_model_layer_matches_dense():
    """apply_mla's paged-latent branch must reproduce the dense
    full-sequence MLA layer on a single prompt (the layer-level version
    of the engine==sequential invariant)."""
    import dataclasses as _dc

    from repro.configs.base import MLAConfig, get_config, smoke_variant
    from repro.models import mla as mla_mod

    cfg = smoke_variant(get_config("deepseek-v3-671b")).replace(
        d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16))
    params = mla_mod.init_mla(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 10, cfg.d_model))
    y_dense, _ = mla_mod.apply_mla(params, x, cfg)
    a = cfg.mla
    cache = {"ckv": jnp.zeros((9, 8, a.kv_lora_rank)),
             "krope": jnp.zeros((9, 8, a.qk_rope_head_dim)),
             "block_tables": jnp.asarray([[3, 1, 0, 0]], jnp.int32)}
    y_paged, new_cache = mla_mod.apply_mla(
        cache=cache, x=x, cfg=cfg, params=params,
        pos=jnp.asarray([0]), valid_len=jnp.asarray([10]))
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_paged),
                               atol=3e-5, rtol=3e-5)
    # no scatter outside the row's block table
    assert float(jnp.abs(new_cache["ckv"][4:]).max()) == 0.0


# ---------------------------------------------------------------------------
# SSD intra-chunk kernel (Mamba-2)
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # bc, l, h, p, n
    (2, 32, 4, 64, 128),
    (1, 16, 2, 32, 64),     # unaligned p/n: pads to 128 lanes
    (3, 64, 1, 128, 128),
]


@pytest.mark.parametrize("case", SSD_SHAPES)
@pytest.mark.parametrize("dt_", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_sweep(case, dt_):
    bc, l, h, p, n = case
    ks = jax.random.split(jax.random.key(l + h), 5)
    x = (jax.random.normal(ks[0], (bc, l, h, p)) * 0.5).astype(dt_)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bc, l, h)))
    dA = -jnp.cumsum(jax.nn.softplus(
        jax.random.normal(ks[2], (bc, l, h))) * 0.1, axis=1)
    B = (jax.random.normal(ks[3], (bc, l, h, n)) * 0.5).astype(dt_)
    C = (jax.random.normal(ks[4], (bc, l, h, n)) * 0.5).astype(dt_)
    y1, s1 = ops.ssd_chunk(x, dt, dA, B, C)
    y2, s2 = ref.ssd_chunk_bchp(x, dt, dA, B, C)
    np.testing.assert_allclose(np.float32(y1), np.float32(y2), **_tol(dt_))
    np.testing.assert_allclose(np.float32(s1), np.float32(s2), **_tol(dt_))


def test_ssd_chunked_pallas_matches_jnp_end_to_end():
    """Whole SSD (kernel intra-chunk + jnp inter-chunk) == pure jnp."""
    from repro.models import ssm
    h, p, n, s, chunk = 2, 64, 32, 48, 16
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (1, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (1, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (1, s, 1, n)) * 0.5
    y1, f1 = ssm.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    y2, f2 = ssm.ssd_chunked_pallas(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-4,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# view-resident decode attend (N-step loop kview branch)
# ---------------------------------------------------------------------------

VIEW_SHAPES = [
    # b, s(view incl. trash slot), kv, g, hd, window
    (3, 41, 2, 3, 48, 0),       # odd S, unaligned hd
    (2, 129, 1, 4, 64, 0),
    (2, 257, 4, 2, 128, 20),    # aligned hd + sliding window
    (4, 65, 2, 1, 96, 7),
]


@pytest.mark.parametrize("case", VIEW_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_attend_view_kernel_sweep(case, dt):
    """ops.decode_view_attend vs the model's jnp view attend
    (attention.paged_decode_attention — the exec-path oracle the kernel
    replaces inside the fori_loop).  The last view slot plays the trash
    row: it holds garbage and live positions never reach it."""
    from repro.models import attention as mattn
    b, s, kv, g, hd, window = case
    h = kv * g
    ks = jax.random.split(jax.random.key(sum(case)), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dt)
    rng = np.random.default_rng(sum(case))
    # live rows satisfy pos <= sview - 1 = s - 2 (slot s-1 is trash)
    pos = jnp.asarray(rng.integers(0, s - 1, (b,)), jnp.int32)
    o1 = ops.decode_view_attend(q, k, v, pos, window=window)
    o2 = mattn.paged_decode_attention(
        q.reshape(b, 1, kv, g, hd), k, v, pos[:, None],
        window=window).reshape(b, h, hd)
    assert o1.shape == (b, h, hd) and o1.dtype == q.dtype
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


def test_attend_view_kernel_ignores_trash_and_frontier_garbage():
    """Poisoning every slot past each row's position (including the
    trash slot) with huge values must not change the output."""
    b, s, kv, g, hd = 2, 33, 2, 2, 64
    h = kv * g
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    pos = jnp.asarray([10, 31], jnp.int32)
    o_clean = ops.decode_view_attend(q, k, v, pos)
    mask = jnp.arange(s)[None, :, None, None] > pos[:, None, None, None]
    k_bad = jnp.where(mask, 1e4, k)
    v_bad = jnp.where(mask, -1e4, v)
    o_pois = ops.decode_view_attend(q, k_bad, v_bad, pos)
    np.testing.assert_allclose(np.asarray(o_clean), np.asarray(o_pois),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# MLA latent attends (absorbed-query, view + paged pool forms)
# ---------------------------------------------------------------------------

MLA_VIEW_SHAPES = [
    # b, c, h, r, rd, s
    (2, 1, 4, 24, 12, 37),      # odd everything (lane-pads r/rd/S)
    (3, 1, 2, 128, 128, 128),   # aligned fast path
    (2, 3, 4, 16, 8, 65),       # chunked queries
]


@pytest.mark.parametrize("case", MLA_VIEW_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_mla_latent_kernel_views_sweep(case, dt):
    b, c, h, r, rd, s = case
    ks = jax.random.split(jax.random.key(sum(case)), 4)
    q_lat = jax.random.normal(ks[0], (b, c, h, r), jnp.float32).astype(dt)
    q_rope = jax.random.normal(ks[1], (b, c, h, rd), jnp.float32).astype(dt)
    ckv = jax.random.normal(ks[2], (b, s, r), jnp.float32).astype(dt)
    kr = jax.random.normal(ks[3], (b, s, rd), jnp.float32).astype(dt)
    rng = np.random.default_rng(sum(case))
    pos = jnp.asarray(rng.integers(0, s - c, (b,)), jnp.int32)
    scale = 1.0 / np.sqrt(r + rd)
    o1 = ops.mla_decode_views(q_lat, q_rope, ckv, kr, pos, scale=scale)
    o2 = ref.mla_decode_views(q_lat, q_rope, ckv, kr, pos, scale=scale)
    assert o1.shape == (b, c, h, r) and o1.dtype == q_lat.dtype
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


@pytest.mark.parametrize("case", MLA_PAGED_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_mla_latent_kernel_paged_sweep(case, dt):
    """The block table rides in scalar prefetch; disjoint shuffled
    non-trash blocks per row, trash block 0 backing every unassigned
    table entry."""
    nb, bs, r, rd, b, c, h, nb_seq = case
    ks = jax.random.split(jax.random.key(sum(case)), 4)
    q_lat = jax.random.normal(ks[0], (b, c, h, r), jnp.float32).astype(dt)
    q_rope = jax.random.normal(ks[1], (b, c, h, rd), jnp.float32).astype(dt)
    ckv = jax.random.normal(ks[2], (nb, bs, r), jnp.float32).astype(dt)
    kr = jax.random.normal(ks[3], (nb, bs, rd), jnp.float32).astype(dt)
    rng = np.random.default_rng(nb)
    perm = rng.permutation(np.arange(1, nb))[:b * nb_seq]
    bt = jnp.asarray(perm.reshape(b, nb_seq), jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb_seq * bs - c + 1, (b,)), jnp.int32)
    scale = 1.0 / np.sqrt(r + rd)
    o1 = ops.mla_decode_paged(q_lat, q_rope, ckv, kr, bt, pos, scale=scale)
    o2 = ref.mla_decode_paged(q_lat, q_rope, ckv, kr, bt, pos, scale=scale)
    assert o1.shape == (b, c, h, r)
    np.testing.assert_allclose(np.float32(o1), np.float32(o2), **_tol(dt))


def test_mla_latent_kernel_trash_table_rows_are_masked():
    """Rows whose table is mostly trash block 0 (short sequences) must
    ignore the trash pool contents entirely: poisoning block 0 changes
    nothing."""
    nb, bs, r, rd, b, h, nb_seq = 8, 8, 32, 16, 2, 2, 3
    ks = jax.random.split(jax.random.key(11), 4)
    q_lat = jax.random.normal(ks[0], (b, 1, h, r))
    q_rope = jax.random.normal(ks[1], (b, 1, h, rd))
    ckv = jax.random.normal(ks[2], (nb, bs, r))
    kr = jax.random.normal(ks[3], (nb, bs, rd))
    bt = jnp.asarray([[3, 0, 0], [5, 6, 0]], jnp.int32)
    pos = jnp.asarray([4, 11], jnp.int32)     # inside the real blocks
    scale = 1.0 / np.sqrt(r + rd)
    o_clean = ops.mla_decode_paged(q_lat, q_rope, ckv, kr, bt, pos,
                                   scale=scale)
    o_pois = ops.mla_decode_paged(
        q_lat, q_rope, ckv.at[0].set(1e4), kr.at[0].set(1e4), bt, pos,
        scale=scale)
    np.testing.assert_allclose(np.asarray(o_clean), np.asarray(o_pois),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# slot-state gather/scatter (ssm/rglru recurrent pools)
# ---------------------------------------------------------------------------

SLOT_SHAPES = [
    # S, B, feature dims
    (11, 4, (3, 17)),      # conv-tail-like, odd feature size
    (5, 4, (64,)),         # 1-D state, B == #live (non-trash) slots
    (33, 2, (4, 2, 32)),   # SSD-state-like 3-D features
    (9, 3, (128,)),        # lane-aligned fast path
]


@pytest.mark.parametrize("case", SLOT_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_slot_state_kernel_gather_sweep(case, dt):
    s, b, feat = case
    rng = np.random.default_rng(s + b)
    pool = jnp.asarray(rng.standard_normal((s,) + feat), jnp.float32
                       ).astype(dt)
    slots = jnp.asarray(rng.permutation(np.arange(1, s))[:b], jnp.int32)
    fresh = jnp.asarray(rng.integers(0, 2, (b,)).astype(bool))
    got = ops.slot_gather(pool, slots, fresh)
    mask = np.asarray(fresh).reshape((b,) + (1,) * len(feat))
    want = np.where(mask, 0, np.asarray(jnp.float32(pool))[np.asarray(slots)])
    assert got.shape == (b,) + feat and got.dtype == pool.dtype
    np.testing.assert_allclose(np.float32(got), want, atol=0, rtol=0)


@pytest.mark.parametrize("case", SLOT_SHAPES)
def test_slot_state_kernel_scatter_sweep(case):
    """Exact equality with layers.slot_state_scatter: valid rows land in
    their slot, valid_len == 0 rows route to trash slot 0, untouched
    pool rows copy through bit-identically."""
    from repro.models.layers import slot_state_scatter
    s, b, feat = case
    rng = np.random.default_rng(s * b)
    pool = jnp.asarray(rng.standard_normal((s,) + feat), jnp.float32)
    slots = jnp.asarray(rng.permutation(np.arange(1, s))[:b], jnp.int32)
    value = jnp.asarray(rng.standard_normal((b,) + feat), jnp.float32)
    vl = jnp.asarray(rng.integers(0, 3, (b,)), jnp.int32)
    got = np.asarray(ops.slot_scatter(pool, slots, vl, value))
    want = np.asarray(slot_state_scatter(pool, slots, vl, value))
    # trash slot 0 content is unspecified when several valid-0 rows
    # collide there; everything else must match exactly
    np.testing.assert_array_equal(got[1:], want[1:])
    # unconditional form (the loop's view write-back): exact everywhere
    got2 = np.asarray(ops.slot_scatter(pool, slots, None, value))
    want2 = np.asarray(slot_state_scatter(pool, slots, None, value))
    np.testing.assert_array_equal(got2, want2)


def test_slot_state_kernel_vmapped_over_layers():
    """The decode loop vmaps the kernels over the stacked layer axis;
    gather∘scatter round-trips the pool."""
    l, s, b, f = 3, 7, 4, 48
    rng = np.random.default_rng(12)
    pool = jnp.asarray(rng.standard_normal((l, s, f)), jnp.float32)
    slots = jnp.asarray([2, 4, 1, 6], jnp.int32)
    fresh = jnp.zeros((b,), bool)
    g = jax.vmap(lambda p: ops.slot_gather(p, slots, fresh))(pool)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray(pool[:, slots]))
    back = jax.vmap(lambda p, v: ops.slot_scatter(p, slots, None, v))(
        pool, g)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(pool))


# ---------------------------------------------------------------------------
# fused sampling (greedy / gumbel / top-k + gumbel)
# ---------------------------------------------------------------------------

SAMPLING_SHAPES = [
    # b, v
    (5, 203),      # odd vocab (pads past one block)
    (2, 512),      # exactly one block
    (3, 1000),     # multi-block, unaligned
    (8, 4096),
]


@pytest.mark.parametrize("case", SAMPLING_SHAPES)
def test_sampling_kernel_greedy_exact(case):
    """Token-identical to jnp.argmax, including first-occurrence ties
    planted across block boundaries."""
    b, v = case
    rng = np.random.default_rng(v)
    lg = jnp.asarray(rng.standard_normal((b, v)) * 3, jnp.float32)
    top = float(lg.max()) + 1.0
    # exact tie in row 0 spanning blocks: argmax must take the first
    lg = lg.at[0, 7].set(top).at[0, v - 1].set(top)
    keys = ref.sample_keys(0, np.arange(b), np.arange(b))
    got = ops.sample_tokens(lg, keys, temperature=0.0, impl="pallas")
    want = ref.sample_tokens(lg, keys, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got[0]) == 7


@pytest.mark.parametrize("case", SAMPLING_SHAPES)
@pytest.mark.parametrize("top_k", [0, 1, 17, 64])
def test_sampling_kernel_matches_oracle_exactly(case, top_k):
    """The fused kernel must reproduce ref.sample_tokens bit-exactly
    (same keys → same tokens), not merely in distribution: categorical
    IS gumbel-max and the kernel replays the oracle's float ops in the
    same order."""
    b, v = case
    rng = np.random.default_rng(v + top_k)
    lg = jnp.asarray(rng.standard_normal((b, v)) * 2, jnp.float32)
    keys = ref.sample_keys(7, rng.integers(0, 1 << 20, (b,)),
                           rng.integers(0, 4096, (b,)))
    for temp in (0.7, 1.0):
        got = ops.sample_tokens(lg, keys, temperature=temp, top_k=top_k,
                                impl="pallas")
        want = ref.sample_tokens(lg, keys, temperature=temp, top_k=top_k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sampling_kernel_topk_duplicate_kth_values():
    """lax.top_k's kth threshold keeps ALL entries tied with it; the
    kernel's iterative max-extraction must agree when the kth value is
    duplicated (and under -inf-masked vocab entries)."""
    b, v, k = 3, 600, 8
    rng = np.random.default_rng(3)
    lg = np.asarray(rng.standard_normal((b, v)) * 2, np.float32)
    lg[0, 100:120] = 1.5          # 20 copies straddling the kth position
    lg[1, :300] = -np.inf         # half the vocab masked out
    lg = jnp.asarray(lg)
    keys = ref.sample_keys(1, np.arange(b) + 5, np.arange(b) * 7)
    got = ops.sample_tokens(lg, keys, temperature=0.9, top_k=k,
                            impl="pallas")
    want = ref.sample_tokens(lg, keys, temperature=0.9, top_k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sampling_kernel_under_jit_and_engine_keying():
    """The pallas sampler must be jit-stable with the engine's exact key
    derivation (fold_in(rid, position)) and agree with the oracle inside
    the same jit."""
    b, v = 4, 300
    lg = jax.random.normal(jax.random.key(0), (b, v)) * 2

    @jax.jit
    def both(rids, positions):
        keys = ref.sample_keys(0, rids, positions)
        return (ops.sample_tokens(lg, keys, temperature=0.8, top_k=12,
                                  impl="pallas"),
                ref.sample_tokens(lg, keys, temperature=0.8, top_k=12))

    got, want = both(jnp.arange(b) + 100, jnp.arange(b) * 3 + 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# kernel_spec coverage: every advertised kernel is a real ops function
# ---------------------------------------------------------------------------

def test_kernel_spec_names_real_ops():
    from repro.configs.base import get_config, smoke_variant
    from repro.models.model import build_model
    for name in ("qwen2-1.5b", "deepseek-v3-671b", "mamba2-370m",
                 "recurrentgemma-2b"):
        model = build_model(smoke_variant(get_config(name)))
        spec = dict(model.paged_spec.kernel_spec)
        assert "sampling" in spec
        for kind, entry in spec.items():
            for op_name in entry.split("/"):
                assert callable(getattr(ops, op_name)), (name, kind, op_name)
