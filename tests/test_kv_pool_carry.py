"""The serve programs' layer loop against a plain Python loop over layers.

`models/gpt.py` holds the KV pools `[L, NB, BS, H*D]` in the carry of its
layer scan and addresses them in place by (layer, block, slot)
(`_serve_layers`, PERF.md section 6, PR 25). All four programs are one
thing seen row by row: a row has a token, a position and a block table; it
writes its K/V at (layer, table[pos // BS], pos % BS) and attends over its
table's context up to its position. The reference below does exactly that
with numpy indexing, one layer and one row at a time, on pools that start
out full of noise, so a write to a wrong layer, block or slot, or a read
from one, shows in the tokens or in the pools."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import gpt
from paddle_tpu.serving.kv_cache import (KVCacheConfig, NULL_BLOCK,
                                         init_pools)

BS, NB, MB = 8, 12, 4
EOS = -1


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig(vocab_size=97, hidden=32, layers=3, heads=4,
                        mlp_dim=64, max_len=MB * BS, dtype="float32")
    params, _ = gpt.init(jax.random.key(5), cfg)
    kv = KVCacheConfig(layers=cfg.layers, kv_heads=cfg.heads,
                       head_dim=cfg.head_dim, max_len=cfg.max_len,
                       block_size=BS, num_blocks=NB, dtype="float32")
    shape = init_pools(kv)[0].shape
    assert shape == (cfg.layers, NB, BS, cfg.hidden)
    rng = np.random.default_rng(11)
    kp = rng.standard_normal(shape).astype(np.float32)
    vp = rng.standard_normal(shape).astype(np.float32)
    return cfg, params, kp, vp


def _plain_rows(cfg, params, ids, pos, tables, kp, vp):
    """ids [N], pos [N], tables [N, MB] -> (hidden rows [N, H], kp, vp):
    a Python loop over layers, and over rows for every pool access."""
    n, nh, hd = len(ids), cfg.heads, cfg.head_dim
    kp, vp = kp.copy(), vp.copy()
    x = np.asarray(params["wte.w"])[ids] + np.asarray(params["wpe.w"])[
        np.minimum(pos, cfg.max_len - 1)]
    x = jnp.asarray(x, jnp.float32)
    for l in range(cfg.layers):
        lp = {k: v[l] for k, v in params.items() if k.startswith("blk.")}
        y = gpt._ln(x, lp["blk.ln1.scale"], lp["blk.ln1.bias"])
        q, k, v = (np.asarray(a) for a in gpt._qkv(lp, y))
        for r in range(n):                     # every row writes ...
            bi = pos[r] // BS
            blk = tables[r, bi] if bi < MB else NULL_BLOCK
            kp[l, blk, pos[r] % BS] = k[r]
            vp[l, blk, pos[r] % BS] = v[r]
        ctx = np.zeros((n, cfg.hidden), np.float32)
        for r in range(n):                     # ... before any row reads
            keys = kp[l, tables[r]].reshape(MB * BS, nh, hd)
            vals = vp[l, tables[r]].reshape(MB * BS, nh, hd)
            s = np.einsum("nd,mnd->nm", q[r].reshape(nh, hd), keys)
            s = np.where(np.arange(MB * BS)[None] <= pos[r],
                         s / math.sqrt(hd), -1e9)
            att = np.exp(s - s.max(-1, keepdims=True))
            att /= att.sum(-1, keepdims=True)
            ctx[r] = np.einsum("nm,mnd->nd", att, vals).reshape(-1)
        x = gpt._proj(lp, jnp.asarray(ctx), x)
        y = gpt._ln(x, lp["blk.ln2.scale"], lp["blk.ln2.bias"])
        x = x + gpt._decode_mlp(lp, y)
    return x, kp, vp


def _tables(*rows):
    out = np.zeros((len(rows), MB), np.int32)
    for i, blocks in enumerate(rows):
        out[i, :len(blocks)] = blocks
    return out


def _case(kind, cfg, params, kp, vp):
    """Run one program and the plain loop on the same inputs:
    (tokens, kp, vp) of each."""
    rng = np.random.default_rng(17)
    kw = dict(block_size=BS, eos_id=EOS)
    pools = (jnp.asarray(kp), jnp.asarray(vp))

    def head(x, rows, prev):
        return np.asarray(gpt._head(params, x[rows], jnp.asarray(prev), EOS))

    if kind == "decode":            # three slots, the third inactive
        ids = rng.integers(0, 97, 3).astype(np.int32)
        pos = np.array([5, 17, 0], np.int32)
        tables = _tables([3, 4], [7, 1, 9], [])
        got = gpt.apply_decode_step(params, cfg, ids, pos, *pools,
                                    jnp.asarray(tables), **kw)
        x, rk, rv = _plain_rows(cfg, params, ids, pos, tables, kp, vp)
        return got, (head(x, np.arange(3), ids), rk, rv)
    if kind == "verify":            # W = 3 tokens a slot; a span that
        ids = rng.integers(0, 97, (2, 3)).astype(np.int32)  # crosses a block
        pos = np.array([6, 13], np.int32)
        tables = _tables([2, 5], [8, 6, 10])
        got = gpt.apply_verify_step(params, cfg, ids, pos, *pools,
                                    jnp.asarray(tables), **kw)
        x, rk, rv = _plain_rows(
            cfg, params, ids.reshape(-1),
            (pos[:, None] + np.arange(3)[None]).reshape(-1),
            np.repeat(tables, 3, axis=0), kp, vp)
        return got, (head(x, np.arange(6), ids.reshape(-1)).reshape(2, 3),
                     rk, rv)
    if kind == "prefill":           # bucket 16, 5 real tokens, ONE block:
        ids = rng.integers(0, 97, (1, 16)).astype(np.int32)  # the padded
        length, table = 5, _tables([4])[0]     # tail lands in the null block
        got = gpt.apply_prefill(params, cfg, ids, np.int32(length), *pools,
                                jnp.asarray(table), **kw)
        pos = np.arange(16, dtype=np.int32)
    else:                           # chunk: positions 8..15 of a 13-token
        ids = rng.integers(0, 97, (1, 8)).astype(np.int32)  # prompt whose
        length, table = 13, _tables([6, 2])[0]  # first chunk is in the pool
        got = gpt.apply_prefill_chunk(params, cfg, ids, np.int32(8),
                                      np.int32(length), *pools,
                                      jnp.asarray(table), **kw)
        pos = np.arange(8, 16, dtype=np.int32)
    x, rk, rv = _plain_rows(cfg, params, ids[0], pos,
                            np.repeat(table[None], len(pos), axis=0), kp, vp)
    last = length - 1 - int(pos[0])
    return got, (head(x, np.array([last]), ids[0, [last]]), rk, rv)


@pytest.mark.parametrize("kind", ["decode", "prefill", "chunk", "verify"])
def test_program_equals_plain_loop_over_layers(model, kind):
    cfg, params, kp, vp = model
    (tok, gk, gv), (rtok, rk, rv) = _case(kind, cfg, params, kp, vp)
    np.testing.assert_array_equal(np.asarray(tok), rtok)
    for got, ref, before in ((gk, rk, kp), (gv, rv, vp)):
        got = np.asarray(got)
        assert got.shape == before.shape and got.dtype == before.dtype
        # the null block takes every padded / inactive write, in no order
        got, ref, before = got[:, 1:], ref[:, 1:], before[:, 1:]
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
        untouched = ref == before
        assert 0 < (~untouched).sum() < untouched.size // 4
        np.testing.assert_array_equal(got[untouched], before[untouched])


# ---------------------------------------------------------------------------
# The whole-prompt write alone (PERF.md section 6, PR 30). A bucket that is
# whole blocks goes into the pool a block at a time, one update a table
# entry; a bucket under a block keeps the row form. Either way the pool
# holds what a Python loop over the positions writes: buckets like the
# engine's 8 / 16 / 64 / 512 / 1024 scaled to a block of 8, tokens like
# GPT-2-large's 1280 and OLMoE's 2048 lanes scaled down.
# ---------------------------------------------------------------------------

_W_BS, _W_MB, _W_LAYERS = 8, 16, 3


def _allocated(fill, bucket):
    """How many blocks the sequence owns: enough for the whole bucket, for
    half of it (the table's tail entries are 0), or one."""
    whole = -(-bucket // _W_BS)
    return {"full": whole, "partial": max(1, whole // 2), "one_block": 1}[fill]


@pytest.mark.parametrize("width,dtype", [(40, "float32"), (64, "bfloat16")])
@pytest.mark.parametrize("fill", ["full", "partial", "one_block"])
@pytest.mark.parametrize("bucket", [4, 8, 32, 64, 128])
def test_prefill_write_puts_whole_blocks_where_the_rows_went(
        bucket, fill, width, dtype):
    from paddle_tpu.serving import kv_cache as kvc

    rng = np.random.default_rng(bucket + width)
    nb = 2 * _W_MB + 1
    dt = jnp.dtype(dtype)
    before = np.asarray(jnp.asarray(rng.standard_normal(
        (_W_LAYERS, nb, _W_BS, width)), dt))
    kv = np.asarray(jnp.asarray(rng.standard_normal((bucket, width)), dt))
    owned = rng.permutation(np.arange(1, nb))[:_allocated(fill, bucket)]
    table = kvc.build_block_table(owned, _W_MB)
    layer = 1

    kvc.PREFILL_WRITE_UNITS.clear()
    got = jax.jit(lambda p, l, x, t: kvc.write_prefill_kv(p, l, x, t, _W_BS))(
        jnp.asarray(before), jnp.int32(layer), jnp.asarray(kv),
        jnp.asarray(table))
    unit = "blocks" if bucket % _W_BS == 0 else "rows"
    assert kvc.PREFILL_WRITE_UNITS == {unit: 1}, kvc.PREFILL_WRITE_UNITS

    want = before.copy()                    # the row form, a position a time
    for t in range(bucket):
        want[layer, table[t // _W_BS], t % _W_BS] = kv[t]
    got = np.array(got)
    assert got.shape == before.shape and got.dtype == before.dtype
    # every allocated block holds what the rows wrote (the last one its
    # old slots past the bucket's end where the bucket is under a block) ...
    np.testing.assert_array_equal(got[layer, owned], want[layer, owned])
    assert (want[layer, owned[0]] != before[layer, owned[0]]).any()
    # ... and no block the table does not name, of any layer, is touched;
    # the null block takes the positions past the allocation, in any order
    got[layer, owned] = before[layer, owned]
    np.testing.assert_array_equal(got[:, 1:], before[:, 1:])
