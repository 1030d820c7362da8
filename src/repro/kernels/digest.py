"""Content digests of device-resident arrays, computed on the device.

``WorkloadSpec.fingerprint`` keys the session memo and the persistent
counter cache by content.  For an array already on the device, hashing
it on the host would copy it back first (235 MB for one batch of
DeepSeek-V3 hidden states).  Here a jitted program reads each array
where it is and only a few words per array come back.

The digest of an array is four 32-bit lanes.  Each element's bits,
widened to a 32-bit word ``w_i`` (an 8-byte element gives two), are
mixed with its flat position ``i``: lane ``k`` sums
``fmix32(w_i ^ (fmix32(i * G + 1) + K_k))`` modulo 2**32 (``fmix32`` is
MurmurHash3's finaliser, a bijection on 32-bit words).  XLA fuses it
into one pass over the array.  What that gives the memo, in place of
sha256:

* any change of one element changes every lane (a bijection of a
  changed word cannot give the same summand), as does moving a value to
  another position unless it lands on an equal one;
* any other accidental change leaves all four lanes equal with a chance
  of about 2**-128 (four lanes of about 2**-32 each);
* it is not cryptographic: someone who chooses the arrays can build
  collisions (the lanes are sums), so a cache shared with untrusted
  callers should not key on it.

Dtype and shape are not in the digest; the caller frames them beside it.
Positions are taken modulo 2**32, so only arrays of more than 2**32
elements can alias by position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)
_LANE_KEYS = tuple(np.uint32(k) for k in
                   (0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1))
_UNSIGNED = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _fmix32(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _words(a: jnp.ndarray) -> jnp.ndarray:
    """The array's bits as uint32 words, one per element (two for an
    8-byte element), in row-major order."""
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint32)
    size = jnp.dtype(a.dtype).itemsize
    if size == 8:
        return jax.lax.bitcast_convert_type(a, jnp.uint32)
    if size not in _UNSIGNED:
        raise TypeError(f"no device digest for dtype {a.dtype}")
    return jax.lax.bitcast_convert_type(a, _UNSIGNED[size]).astype(jnp.uint32)


def _digest(a: jnp.ndarray) -> jnp.ndarray:
    w = _words(a)
    if w.size == 0:
        return jnp.zeros(len(_LANE_KEYS), jnp.uint32)
    w = w.reshape(-1, w.shape[-1]) if w.ndim else w.reshape(1, 1)
    rows, cols = w.shape
    pos = (jax.lax.broadcasted_iota(jnp.uint32, w.shape, 0) * np.uint32(cols)
           + jax.lax.broadcasted_iota(jnp.uint32, w.shape, 1))
    p = _fmix32(pos * _GOLDEN + np.uint32(1))
    return jnp.stack([jnp.sum(_fmix32(w ^ (p + k)), dtype=jnp.uint32)
                      for k in _LANE_KEYS])


#: one program per (shape, dtype), whichever other arrays are digested
#: beside it: a spec's cached arrays drop out of the call (see
#: ``WorkloadSpec.fingerprint``), so a program over the tuple of the rest
#: would compile anew for each new mix of hits and misses.
_digest_one = jax.jit(_digest)


def digests(arrays) -> list:
    """One 16-byte digest per device-resident array, in order: each
    array's program dispatched in turn, then one explicit copy of all
    their digests."""
    out = jax.device_get([_digest_one(a) for a in arrays])
    return [np.asarray(row, "<u4").tobytes() for row in out]
