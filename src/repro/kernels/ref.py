"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.attention import paged_prefill_sdpa, sdpa_ref

__all__ = ["edm_update_ref", "gossip_axpy_ref", "flash_attention_ref",
           "gather_pages", "paged_attention_ref",
           "paged_prefill_attention_ref"]


def edm_update_ref(x, g, m, psi, *, alpha: float, beta: float):
    """Reference EDM fused-update chain (optimizers.make_edm unfused path)."""
    m_new = beta * m + (1.0 - beta) * g
    psi_new = x - alpha * m_new
    phi = psi_new + x - psi
    return m_new, psi_new, phi


def gossip_axpy_ref(operands, weights):
    """n-ary combine  Σₖ wₖ·operandₖ  with f32 accumulation (matches the
    kernel's bf16 path: one rounding, on the final store)."""
    acc = sum(w * o.astype(jnp.float32) for w, o in zip(weights, operands))
    return acc.astype(operands[0].dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, H, Sq, hd); k, v: (B, K, Sk, hd) — delegates to the model-level
    SDPA oracle (which is itself validated by the serving tests)."""
    out = sdpa_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                   jnp.moveaxis(v, 1, 2), causal=causal, window=window)
    return jnp.moveaxis(out, 2, 1)


def gather_pages(pool, page_table):
    """Dense view of a head-major paged pool: (K, num_pages, page_size,
    hd) gathered through a (B, n_pages) page table → (B,
    n_pages·page_size, K, hd).  Row ``j·page_size + r`` of slot b is row r
    of physical page ``page_table[b, j]`` — the layout the page allocator
    maintains."""
    B, n_pages = page_table.shape
    K, _, page_size, hd = pool.shape
    dense = jnp.take(pool, page_table.reshape(-1), axis=1)
    return dense.reshape(K, B, n_pages * page_size, hd).transpose(1, 2, 0, 3)


def paged_attention_ref(q, k_pool, v_pool, page_table, kv_len, *,
                        page_size: int):
    """Dense oracle for the paged decode-attention kernel: gather each
    slot's pages into a contiguous cache and run the model-level SDPA
    oracle with per-slot valid-length masking.  q: (B, K, G, hd) grouped
    single-token queries (the kernel's layout); returns (B, K, G, hd).

    This is also the op sequence the serving engine's ``attn_impl="ref"``
    path executes — the engine-vs-dense divergence gate compares two
    runs of these exact ops (paged gather vs contiguous cache), so it
    asserts EXACT equality (DESIGN §10)."""
    B, K, G, hd = q.shape
    assert k_pool.shape[2] == page_size, (k_pool.shape, page_size)
    k = gather_pages(k_pool, page_table)
    v = gather_pages(v_pool, page_table)
    out = sdpa_ref(q.reshape(B, 1, K * G, hd), k, v, causal=False,
                   kv_len=kv_len)
    return out.reshape(B, K, G, hd)


def paged_prefill_attention_ref(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                                chunk_start, chunk_len, *, window: int = 0):
    """Dense oracle for the paged prefill-attention kernel (DESIGN §11):
    gather the slot's pages, concatenate the in-flight chunk's dense
    keys/values, and run the positional SDPA oracle with ring-aware
    key positions and per-element window masking.  q: (1, C, H, hd)
    model-layout chunk queries; k_chunk, v_chunk: (1, C, K, hd);
    pt_row: (n_pages,); returns (1, C, H, hd).

    This is also the op sequence ``attn_impl="ref"`` executes inside the
    chunked serving engine (:func:`repro.models.attention.paged_prefill_sdpa`
    — same function), so the engine-vs-oracle gate is exact equality."""
    return paged_prefill_sdpa(q, k_chunk, v_chunk, k_pool, v_pool, pt_row,
                              chunk_start, chunk_len, window=window)
