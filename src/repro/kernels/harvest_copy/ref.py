"""Oracle for the harvest gather/scatter data movers."""
import jax.numpy as jnp


def harvest_gather_ref(src_pool, slot_ids):
    return jnp.take(src_pool, slot_ids, axis=0)


def harvest_scatter_ref(dst_pool, staging, slot_ids):
    return dst_pool.at[slot_ids].set(staging.astype(dst_pool.dtype),
                                     mode="drop")


def harvest_copy_ref(src_pool, dst_pool, src_ids, dst_ids):
    """Fused gather->scatter oracle (no staging buffer)."""
    return dst_pool.at[dst_ids].set(
        jnp.take(src_pool, src_ids, axis=0).astype(dst_pool.dtype))


def quantize_demote_ref(src_pool, slot_ids, fidelity: str = "int8"):
    """Dense oracle for the fused quantize kernel: gather, per-row absmax
    scale, quantize, pack."""
    from repro.kernels.harvest_copy.kernel import (FIDELITY_QMAX,
                                                   _padded_elems)
    rows = jnp.take(src_pool, slot_ids, axis=0).astype(jnp.float32)
    pad = _padded_elems(rows.shape[1]) - rows.shape[1]
    rows = jnp.pad(rows, ((0, 0), (0, pad)))
    absmax = jnp.max(jnp.abs(rows), axis=1, keepdims=True)
    scales = jnp.where(absmax == 0.0, 1.0,
                       absmax / FIDELITY_QMAX[fidelity])
    x = rows / scales
    if fidelity == "int8":
        values = jnp.clip(jnp.round(x), -127, 127).astype(jnp.int8)
    elif fidelity == "fp8":
        values = x.astype(jnp.float8_e4m3fn)
    elif fidelity == "int4":
        # element j -> low nibble of byte j, element j + half -> high nibble
        q = jnp.clip(jnp.round(x), -7, 7).astype(jnp.int32)
        lo, hi = jnp.split(q, 2, axis=1)
        values = ((lo & 15) | ((hi & 15) << 4)).astype(jnp.uint8)
    else:
        raise ValueError(f"unknown fidelity {fidelity!r}")
    return values, scales.astype(jnp.float32)


def dequantize_reload_ref(dst_pool, values, scales, slot_ids,
                          fidelity: str = "int8"):
    """Dense oracle for the fused dequantize kernel: unpack, rescale,
    scatter into the pool (untouched slots preserved)."""
    if fidelity == "int4":
        b = values.astype(jnp.int32)
        lo = (b & 15) - 2 * (b & 8)
        hi = ((b >> 4) & 15) - 2 * ((b >> 4) & 8)
        x = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    elif fidelity in ("int8", "fp8"):
        x = values.astype(jnp.float32)
    else:
        raise ValueError(f"unknown fidelity {fidelity!r}")
    rows = (x * scales)[:, :dst_pool.shape[1]]
    return dst_pool.at[slot_ids].set(rows.astype(dst_pool.dtype),
                                     mode="drop")
