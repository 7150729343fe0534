"""Attention variants.

Distribution layout (baseline plan, see EXPERIMENTS.md §Perf for evolution):
  * train / prefill: activations are sharded batch→``data``, seq→``model``.
    Global-attention layers run **ring attention** over the ``model`` axis
    (each device owns an S/n slice of Q and streams KV shards around the
    ring with ``ppermute``) — this supports every GQA head count (1..48)
    on a 16-way axis, unlike head-sharded TP.
  * local (sliding-window) layers gather only ceil(w/S_loc) neighbour
    chunks — O(window) communication instead of the full ring.
  * decode: the KV cache is sharded seq→``model``; each device computes
    partial attention over its slice and the result is combined with
    log-sum-exp weights via one tiny ``psum``.

All functions here are *per-device* bodies meant to run inside
``jax.shard_map``; pure single-device references live next to them for the
(1,1)-mesh smoke/unit tests — the shard-mapped path degenerates to the
reference when the axis size is 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

NEG_INF = -1.0e30


# --------------------------------------------------------------------------- #
# flash-style block update
# --------------------------------------------------------------------------- #

def _flash_block(q, k, v, mask, scale, m, lse, acc):
    """One online-softmax update.

    q: (B, C, KV, G, D)   k/v: (B, S, KV, D)   mask: (C, S) or (B, C, S)
    m, lse: (B, C, KV, G)   acc: (B, C, KV, G, D)  (all fp32)
    """
    s = jnp.einsum("bckgd,bskd->bckgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask_b = mask[None, :, None, None, :]
    else:
        mask_b = mask[:, :, None, None, :]
    s = jnp.where(mask_b, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask_b, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = lse * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bckgs,bskd->bckgd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def _split_heads(q, n_kv):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _merge_heads(x):
    b, s, kv, g, d = x.shape
    return x.reshape(b, s, kv * g, d)


def _chunk_count(seq: int, chunk: int) -> int:
    chunk = min(chunk, seq) if chunk else seq
    while seq % chunk:
        chunk -= 1
    return seq // chunk


# Recompute the softmax block in backward (FA2-style): without this, AD
# stores the (B, C, KV, G, S) probability tensor for every (ring x q-chunk)
# block — hundreds of GB at production shapes.
_flash_block_ckpt = jax.checkpoint(_flash_block, static_argnums=(4,))


def _attend_chunked(q, k, v, q_pos, kv_pos, scale, window: int,
                    q_chunk: int, unroll: bool):
    """Chunked (over Q) causal attention of local q against a kv buffer.

    q: (B, Sq, H, D); k/v: (B, Skv, KV, D); q_pos: (Sq,); kv_pos: (Skv,)
    Returns fp32 (m, lse, acc) with shapes ((B,Sq,KV,G), ..., (B,Sq,KV,G,D)).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qs = _split_heads(q, kvh)

    def mask_for(qp):
        m = qp[:, None] >= kv_pos[None, :]
        m &= kv_pos[None, :] >= 0
        if window:
            m &= (qp[:, None] - kv_pos[None, :]) < window
        return m

    nc = _chunk_count(sq, q_chunk)
    c = sq // nc
    m0 = jnp.full((b, sq, kvh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    if nc == 1:
        return _flash_block_ckpt(qs, k, v, mask_for(q_pos), scale, m0, l0, a0)

    qc = qs.reshape(b, nc, c, kvh, g, d).swapaxes(0, 1)
    pc = q_pos.reshape(nc, c)

    def one(_, xs):
        qi, pi = xs
        mi = jnp.full((b, c, kvh, g), NEG_INF, jnp.float32)
        li = jnp.zeros((b, c, kvh, g), jnp.float32)
        ai = jnp.zeros((b, c, kvh, g, d), jnp.float32)
        return None, _flash_block_ckpt(qi, k, v, mask_for(pi), scale,
                                       mi, li, ai)

    if unroll:
        outs = [one(None, (qc[i], pc[i]))[1] for i in range(nc)]
        m, lse, acc = (jnp.stack([o[j] for o in outs]) for j in range(3))
    else:
        _, (m, lse, acc) = jax.lax.scan(one, None, (qc, pc))
    m = m.swapaxes(0, 1).reshape(b, sq, kvh, g)
    lse = lse.swapaxes(0, 1).reshape(b, sq, kvh, g)
    acc = acc.swapaxes(0, 1).reshape(b, sq, kvh, g, d)
    return m, lse, acc


def _merge_state(state_a, state_b):
    """Combine two online-softmax partial states."""
    m_a, l_a, a_a = state_a
    m_b, l_b, a_b = state_b
    m = jnp.maximum(m_a, m_b)
    ca, cb = jnp.exp(m_a - m), jnp.exp(m_b - m)
    return m, l_a * ca + l_b * cb, a_a * ca[..., None] + a_b * cb[..., None]


def _finalize(m, lse, acc, dtype):
    out = acc / jnp.maximum(lse, 1e-20)[..., None]
    return _merge_heads(out).astype(dtype)


# --------------------------------------------------------------------------- #
# ring attention (global layers, seq sharded over `axis_name`)
# --------------------------------------------------------------------------- #

def ring_attention(q, k, v, *, axis_name: str, n_shards: int, scale: float,
                   q_chunk: int = 256, unroll: bool = False):
    """Per-device body. q: (B, Sq_loc, H, D); k/v: (B, Skv_loc, KV, D)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if n_shards == 1:
        q_pos = jnp.arange(sq)
        m, lse, acc = _attend_chunked(q, k, v, q_pos, jnp.arange(skv), scale,
                                    0, q_chunk, unroll)
        return _finalize(m, lse, acc, q.dtype)

    my = jax.lax.axis_index(axis_name)
    q_pos = my * sq + jnp.arange(sq)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def compute(j, k, v, state):
        src = (my - j) % n_shards
        kv_pos = src * skv + jnp.arange(skv)
        st = _attend_chunked(q, k, v, q_pos, kv_pos, scale, 0, q_chunk, unroll)
        return _merge_state(state, st)

    state = (jnp.full((b, sq, k.shape[2], h // k.shape[2]), NEG_INF, jnp.float32),
             jnp.zeros((b, sq, k.shape[2], h // k.shape[2]), jnp.float32),
             jnp.zeros((b, sq, k.shape[2], h // k.shape[2], d), jnp.float32))

    if unroll:
        for j in range(n_shards):
            state = compute(j, k, v, state)
            if j != n_shards - 1:
                k = jax.lax.ppermute(k, axis_name, perm)
                v = jax.lax.ppermute(v, axis_name, perm)
    else:
        def ring_step(j, carry):
            k, v, state = carry
            state = compute(j, k, v, state)
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
            return (k, v, state)

        k, v, state = jax.lax.fori_loop(0, n_shards - 1, ring_step,
                                        (k, v, state))
        state = compute(n_shards - 1, k, v, state)
    return _finalize(*state, q.dtype)


# --------------------------------------------------------------------------- #
# local (sliding-window) attention, seq sharded over `axis_name`
# --------------------------------------------------------------------------- #

def local_attention(q, k, v, *, axis_name: str, n_shards: int, scale: float,
                    window: int, q_chunk: int = 256, unroll: bool = False):
    """Per-device body. Gathers ceil(window/S_loc) neighbour KV chunks."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    my = jax.lax.axis_index(axis_name) if n_shards > 1 else 0
    q_pos = my * sq + jnp.arange(sq)

    n_prev = min(-(-window // skv), n_shards - 1)  # ceil, capped
    parts_k, parts_v = [k], [v]
    if n_shards > 1 and n_prev > 0:
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        pk, pv = k, v
        for _ in range(n_prev):
            pk = jax.lax.ppermute(pk, axis_name, perm)
            pv = jax.lax.ppermute(pv, axis_name, perm)
            parts_k.insert(0, pk)
            parts_v.insert(0, pv)
    k_ext = jnp.concatenate(parts_k, axis=1)
    v_ext = jnp.concatenate(parts_v, axis=1)
    start = (my - len(parts_k) + 1) * skv
    kv_pos = start + jnp.arange(k_ext.shape[1])  # negative => masked
    m, lse, acc = _attend_chunked(q, k_ext, v_ext, q_pos, kv_pos, scale,
                                window, q_chunk, unroll)
    return _finalize(m, lse, acc, q.dtype)


# --------------------------------------------------------------------------- #
# decode: one new token against a seq-sharded KV cache
# --------------------------------------------------------------------------- #

def quantize_kv(x):
    """Per-(token, head) symmetric int8 quantization.

    x: (B, KV, D) -> (int8 (B, KV, D), f16 scale (B, KV)).
    Beyond-paper optimization: KV streaming dominates the decode memory
    roofline term; int8 storage halves it vs bf16 with <0.5% logit error
    (validated in tests/test_consistency_int8.py).
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    # quantize against the f16-rounded scale that dequantization will use,
    # so the s/2 round-off bound holds for the stored representation
    scale = scale.astype(jnp.float16)
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale[..., None].astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_update_cache(stack, new, layer, pos, my, s_loc):
    """Write `new` (B, KV, ...) at position `pos` of layer `layer` of the
    local stacked slice (R, B, KV, S_loc, ...), in place: values (5-d) and
    scales (4-d).  A position outside this shard's range keeps the old row,
    so the guard reads and writes one (B, KV, ...) row, never a layer."""
    with jax.named_scope("kv_update"):
        local = pos - my * s_loc
        ok = (local >= 0) & (local < s_loc)
        idx = jnp.clip(local, 0, s_loc - 1)
        start = (layer, 0, 0, idx) + (0,) * (stack.ndim - 4)
        old = jax.lax.dynamic_slice(
            stack, start, (1, *stack.shape[1:3], 1, *stack.shape[4:]))
        row = jnp.where(ok, new[None, :, :, None].astype(stack.dtype), old)
        stack = jax.lax.dynamic_update_slice(stack, row, start)
        # keep the stack row-major, the layout in which each layer is the
        # operand attention's dots read.  Left free, the TPU compiler lays
        # the stack out so that the row fills whole tiles, and then copies
        # the whole cache into and out of that layout every step, and each
        # layer back for the dots.
        return with_layout_constraint(stack, Layout(tuple(range(stack.ndim))))


def decode_attention_sharded(q, k_cache, v_cache, new_k, new_v, pos, layer, *,
                             axis_name: str, n_shards: int, scale: float,
                             k_scale=None, v_scale=None):
    """Per-device body.

    q: (B, H, D) replicated over `axis_name`; caches: (R, B, KV, S_loc, D)
    local slice of every layer of a segment's block; new_k/new_v: (B, KV, D)
    replicated; pos: scalar index being written; layer: the index into R of
    this layer.  With ``k_scale``/``v_scale`` (R, B, KV, S_loc) the caches
    are int8 and dequantized on the fly (scores scale by k_scale; p scales
    by v_scale).  The new row is written into the stacks in place, and
    attention reads layer `layer` of the updated stacks.
    Returns ((B, H, D) out, updated caches [, updated scales]).
    """
    b, h, d = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    s_loc = k_cache.shape[3]
    my = jax.lax.axis_index(axis_name) if n_shards > 1 else 0
    quant = k_scale is not None

    if quant:
        nk, nks = quantize_kv(new_k)
        nv, nvs = quantize_kv(new_v)
        k_cache = decode_update_cache(k_cache, nk, layer, pos, my, s_loc)
        v_cache = decode_update_cache(v_cache, nv, layer, pos, my, s_loc)
        k_scale = decode_update_cache(k_scale, nks, layer, pos, my, s_loc)
        v_scale = decode_update_cache(v_scale, nvs, layer, pos, my, s_loc)
    else:
        k_cache = decode_update_cache(k_cache, new_k, layer, pos, my, s_loc)
        v_cache = decode_update_cache(v_cache, new_v, layer, pos, my, s_loc)

    def read(stack):
        return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)

    kc, vc = read(k_cache), read(v_cache)
    kv_pos = my * s_loc + jnp.arange(s_loc)
    mask = (kv_pos <= pos)[None, None, None, :]                # (1,1,1,S)
    qs = q.reshape(b, kvh, g, d)
    kk = kc.astype(jnp.bfloat16) if quant else kc
    s = jnp.einsum("bkgd,bksd->bkgs", qs, kk,
                   preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * read(k_scale).astype(jnp.float32)[:, :, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    lse = jnp.sum(p, axis=-1)
    if quant:
        pv = p * read(v_scale).astype(jnp.float32)[:, :, None]
        acc = jnp.einsum("bkgs,bksd->bkgd", pv.astype(jnp.bfloat16),
                         vc.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    else:
        acc = jnp.einsum("bkgs,bksd->bkgd", p, vc.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    if n_shards > 1:
        m_g = jax.lax.pmax(m, axis_name)
        corr = jnp.exp(m - m_g)
        lse = jax.lax.psum(lse * corr, axis_name)
        acc = jax.lax.psum(acc * corr[..., None], axis_name)
    out = acc / jnp.maximum(lse, 1e-20)[..., None]
    outs = (out.reshape(b, h, d).astype(q.dtype), k_cache, v_cache)
    if quant:
        outs += (k_scale, v_scale)
    return outs


def decode_attention_rolling(q, k_cache, v_cache, new_k, new_v, pos, *,
                             scale: float, window: int):
    """Rolling-window cache decode (local-attention layers).

    q: (B, H, D); caches: (B, W, KV, D) rolling; pos: current position.
    """
    b, h, d = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    w = k_cache.shape[1]
    slot = pos % w
    with jax.named_scope("kv_update"):
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, new_k[:, None].astype(k_cache.dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, new_v[:, None].astype(v_cache.dtype), (0, slot, 0, 0))
    slots = jnp.arange(w)
    # global position stored in each slot (largest p <= pos with p % w == slot)
    kv_pos = pos - ((pos - slots) % w)
    mask = ((kv_pos >= 0) & (kv_pos <= pos)
            & ((pos - kv_pos) < window))[None, None, None, :]
    qs = q.reshape(b, kvh, g, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qs, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    lse = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(lse, 1e-20)[..., None]
    return out.reshape(b, h, d).astype(q.dtype), k_cache, v_cache


# --------------------------------------------------------------------------- #
# single-device reference (tests)
# --------------------------------------------------------------------------- #

def attention_ref(q, k, v, scale: float, window: int = 0, causal: bool = True):
    """Naive softmax attention oracle. q: (B,S,H,D); k/v: (B,S,KV,D)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qs = q.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,bskd->bqkgs", qs.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((s, k.shape[1]), bool)
    if causal:
        mask &= qp >= kp
    if window:
        mask &= (qp - kp) < window
    logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)
