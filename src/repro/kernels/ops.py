"""Public jit'd kernel API with implementation dispatch.

impl resolution:
  'auto'      -> Pallas kernel on TPU backends, chunked-jnp reference
                 elsewhere (CPU container, dry-run lowering);
  'pallas'    -> force the Pallas kernel (compiled for TPU);
  'interpret' -> Pallas kernel in interpret mode (CPU correctness tests);
  'ref'       -> pure-jnp oracle.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _backend() -> str:
    return jax.default_backend()


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if _backend() == "tpu" else "ref"


# ----------------------------------------------------------------- attention
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    impl: str = "auto"):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import flash_attention as fk
        return fk.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap,
                                  interpret=(mode == "interpret"))
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    softcap=softcap)


def decode_attention(q, k, v, kv_len, *, window: Optional[int] = None,
                     softcap: Optional[float] = None, impl: str = "auto"):
    if impl == "auto":
        from repro.distributed import ctx
        if ctx.model_axis_size() > 1 and k.shape[1] % ctx.model_axis_size() == 0:
            from repro.serving.decode import sharded_decode_attention
            return sharded_decode_attention(q, k, v, kv_len, window=window,
                                            softcap=softcap)
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import decode_attention as dk
        return dk.decode_attention(q, k, v, kv_len, window=window,
                                   softcap=softcap,
                                   interpret=(mode == "interpret"))
    return _ref.decode_attention_ref(q, k, v, kv_len, window=window,
                                     softcap=softcap)


# ----------------------------------------------------------------- mamba SSD
def ssd_scan(x, dt, A, B, C, D=None, *, initial_state=None, chunk: int = 128,
             impl: str = "auto"):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import ssd_scan as sk
        return sk.ssd_scan(x, dt, A, B, C, D, initial_state=initial_state,
                           chunk=chunk, interpret=(mode == "interpret"))
    return _ref.ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk,
                             initial_state=initial_state)


def ssd_step(state, x_t, dt_t, A, B_t, C_t, D=None):
    return _ref.ssd_step_ref(state, x_t, dt_t, A, B_t, C_t, D)


# ----------------------------------------------------------- entropy features
def byte_entropy(data, *, impl: str = "auto"):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import entropy_features as ek
        return ek.byte_entropy(data, interpret=(mode == "interpret"))
    return _ref.byte_entropy_ref(data)


def weighted_entropy_features(codes, n_valid, n_rows, n_cols, lengths, *,
                              n_buckets: int = 1, block: int = 16384,
                              impl: str = "auto"):
    """Batched COMPREDICT feature primitive (see kernels/entropy_features.py).

    'ref' is the vmapped-jnp path; 'pallas'/'interpret' run the
    scatter-add histogram plus the vocabulary-tiled reduction kernel.
    Returns (summary (N,4), bucket_H (N,n_buckets))."""
    from repro.kernels import entropy_features as ek
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return ek.weighted_entropy_features(
            codes, n_valid, n_rows, n_cols, lengths, n_buckets=n_buckets,
            block=block, interpret=(mode == "interpret"))
    return ek.weighted_entropy_features_ref(
        codes, n_valid, n_rows, n_cols, lengths, n_buckets=n_buckets)


# --------------------------------------------------------- overlap (DATAPART)
def fractional_overlap_matrix(codes, sizes, spans, *, codes_b=None,
                              spans_b=None, block_f: int = 2048,
                              impl: str = "auto"):
    """Batched G-PART fractional-overlap matrix (see kernels/overlap.py).

    'ref'/'jnp' is the vmapped-jnp oracle, 'numpy' the host fallback;
    'pallas'/'interpret' run the tiled indicator-matmul grid kernel, which
    needs ascending code rows and holds 2 bytes of indicator per (row,
    file) for F files. Returns (NA, NB) f32."""
    from repro.kernels import overlap as ok
    mode = _resolve(impl)
    if mode == "jnp":        # engine backend names alias the jnp oracle
        mode = "ref"
    if mode in ("pallas", "interpret"):
        return ok.fractional_overlap_matrix(
            codes, sizes, spans, codes_b=codes_b, spans_b=spans_b,
            block_f=block_f, interpret=(mode == "interpret"))
    if mode == "numpy":
        return ok.fractional_overlap_matrix_np(
            codes, sizes, spans, codes_b=codes_b, spans_b=spans_b)
    return ok.fractional_overlap_matrix_ref(
        codes, sizes, spans, codes_b=codes_b, spans_b=spans_b)


# ------------------------------------------------------------------- quant8
def quant_pack(x, *, block: int = 256, impl: str = "auto"):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import quant_pack as qk
        return qk.quant_pack(x, block=block, interpret=(mode == "interpret"))
    return _ref.quant_pack_ref(x, block=block)


def quant_unpack(q, scale, dtype=jnp.float32):
    return _ref.quant_unpack_ref(q, scale, dtype)
