"""Public kernel entry points with backend dispatch.

On TPU these call the Pallas kernels (`bgmv.py`, `sgmv.py`,
`flash_decode.py`); everywhere else (CPU tests, host-platform dry-run) they
fall back to the pure-jnp oracles in `ref.py`.  `force` overrides dispatch
('pallas' | 'ref' | 'interpret') — 'interpret' runs the Pallas kernel body
in interpreter mode, which is how the kernel unit tests validate on CPU.

Shared conventions across every entry point:

* adapter ids < 0 mean "base model, no adapter" -> zero LoRA delta;
* ``ranks`` (shape (N,), ranks[i] <= r_max) makes the adapter bank
  ragged: adapter i uses only its first ranks[i] LoRA lanes (padded
  lanes are masked so results are bitwise the dense kernel on a
  ``ref.mask_ragged`` zero-padded bank).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import ref

KERNEL_MODES = ("pallas", "ref", "interpret")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lora_apply(x, a, b, idx, scale: float = 1.0, ranks=None,
               force: str = ""):
    """Multi-adapter LoRA delta: y[t] = scale * x[t] @ A[idx[t]] @ B[idx[t]].

    x: (..., d); idx: per-token adapter ids broadcastable to x's leading
    dims — or per-REQUEST ids of shape (B,) for x of shape (B, S, d).
    a: (N, d, r); b: (N, r, o).  Returns (..., o).  ids < 0 -> zero
    delta; ``ranks`` (N,) enables ragged per-adapter ranks.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    mode = force or ("pallas" if _on_tpu() else "ref")

    if mode == "ref" and x.ndim == 3 and idx.shape == (x.shape[0],):
        # per-request adapters (the serving engine's layout): gather A/B at
        # request granularity — (B, d, r) is tiny — and keep (B, S, d)
        # intact so sharded dims are never reshaped together.
        if ranks is not None:
            a, b = ref.mask_ragged(a, b, ranks)
        idx0 = jnp.maximum(idx, 0)
        ag = jnp.take(a, idx0, axis=0)
        bg = jnp.take(b, idx0, axis=0)
        h = jnp.einsum("bsd,bdr->bsr", x, ag,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        y = jnp.einsum("bsr,bro->bso", h, bg,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        y = jnp.where((idx >= 0)[:, None, None], y, 0)
        return y * jnp.asarray(scale, x.dtype)

    xt = x.reshape(-1, d)
    it = jnp.broadcast_to(idx.reshape(-1, *([1] * (len(lead) - idx.ndim))),
                          lead).reshape(-1) if idx.shape != lead else idx.reshape(-1)
    if mode == "ref":
        if ranks is not None:
            a, b = ref.mask_ragged(a, b, ranks)
        if xt.shape[0] >= 4 * a.shape[0]:
            # token-level ids at prefill size: bucketed SGMV math
            out = ref.lora_ref_bucketed(xt, a, b, it, scale)
        else:
            out = ref.lora_ref(xt, a, b, it, scale)
    else:
        from . import bgmv, sgmv  # lazy: only touch Pallas when requested
        if xt.shape[0] <= a.shape[0] * 4:
            # decode-sized problems -> BGMV (per-token gather); ragged
            # banks are pre-masked (N is small at decode size, the
            # masked bank is cheap and keeps BGMV single-purpose)
            if ranks is not None:
                a, b = ref.mask_ragged(a, b, ranks)
            out = bgmv.bgmv(xt, a, b, it, scale,
                            interpret=(mode == "interpret"))
        else:
            # prefill-sized -> SGMV; interpret follows the same routing
            # so CPU tests exercise the kernel Pallas actually runs
            out = sgmv.sgmv(xt, a, b, it, scale, ranks=ranks,
                            interpret=(mode == "interpret"))
    return out.reshape(*lead, -1)


def flash_decode(q, k, v, length, force: str = ""):
    """Single-token attention against a contiguous KV cache.

    q: (B, H, D); k/v: (B, S, KV, D); length: valid prefix length.
    """
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.flash_decode_ref(q, k, v, length)
    from . import flash_decode as fd
    return fd.flash_decode(q, k, v, length, interpret=(mode == "interpret"))


def fused_decode(q, k, v, length, x, a, b, idx, scale: float = 1.0,
                 ranks=None, force: str = ""):
    """Fused decode step: ``attn(q,K,V) + scale * x @ A[idx] @ B[idx]``.

    One kernel launch per decode step instead of base-then-adapter.
    q: (B, H, D); k/v: (B, S, KV, D); x: (B, dx); a: (N, dx, r);
    b: (N, r, H*D); idx: (B,) adapter ids (< 0 -> base model);
    ``ranks`` (N,) enables ragged per-adapter ranks.  Returns (B, H, D).
    """
    mode = force or ("pallas" if _on_tpu() else "ref")
    if ranks is not None:
        a, b = ref.mask_ragged(a, b, ranks)
    if mode == "ref":
        return ref.fused_decode_ref(q, k, v, length, x, a, b, idx, scale)
    from . import flash_decode as fd
    return fd.flash_decode_lora(q, k, v, length, x, a, b, idx, scale,
                                interpret=(mode == "interpret"))
