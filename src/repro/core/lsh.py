"""Locality-sensitive hashing for the approximation index (paper C4).

Random-hyperplane signatures (Charikar's SimHash): bit i of sig(x) is
``1[r_i . x >= 0]`` for Gaussian hyperplanes r_i.  Then

    Pr[bit_i(x) != bit_i(y)] = angle(x, y) / pi

so with Hamming distance m over L bits,  cos(pi * m / L) ~= cosine(x, y)
and the paper approximates ``exp(w . d)`` by ``exp(cos(pi m / L))``
(Sec. III-B; vectors are unit length after the training modification).

Bits are packed into uint32 lanes; Hamming distance is XOR + popcount —
the exact trick the paper credits for index efficiency.  The packed
kernel lives in kernels/hamming; this module holds the reference
implementation and the index container.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LSHConfig:
    bits: int = 256        # lambda_2; paper uses 100, we use wider + see asym
    seed: int = 7

    @property
    def words(self) -> int:
        if self.bits % 32:
            raise ValueError(f"bits must be a multiple of 32, got {self.bits}")
        return self.bits // 32


def hyperplanes(cfg: LSHConfig, dim: int) -> jax.Array:
    """[bits, dim] Gaussian hyperplanes (fixed seed => reusable index)."""
    key = jax.random.PRNGKey(cfg.seed)
    return jax.random.normal(key, (cfg.bits, dim), jnp.float32)


def signature_bits(x: jax.Array, planes: jax.Array) -> jax.Array:
    """[N, bits] uint8 of raw sign bits for row vectors ``x`` [N, dim]."""
    proj = x @ planes.T
    return (proj >= 0).astype(jnp.uint8)


def pack_bits(bits: jax.Array) -> jax.Array:
    """[N, bits] uint8 -> [N, bits//32] uint32, bit j of word k is
    signature bit 32*k + j (little-endian within the lane)."""
    n, b = bits.shape
    assert b % 32 == 0, b
    lanes = bits.reshape(n, b // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (lanes * weights).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(packed: jax.Array, bits: int) -> jax.Array:
    n, w = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    out = (packed[:, :, None] >> shifts) & jnp.uint32(1)
    return out.reshape(n, w * 32)[:, :bits].astype(jnp.uint8)


def popcount32(x: jax.Array) -> jax.Array:
    """Branch-free popcount over uint32 (classic SWAR bit tricks)."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def hamming_distance(a_packed: jax.Array, b_packed: jax.Array) -> jax.Array:
    """[N, W] x [M, W] -> [N, M] int32 Hamming distance (XOR+popcount)."""
    x = a_packed[:, None, :] ^ b_packed[None, :, :]
    return popcount32(x).sum(axis=-1)


def hamming_similarity(
    a_packed: jax.Array, b_packed: jax.Array, bits: int,
    temperature: float = 1.0,
) -> jax.Array:
    """Paper Sec. III-B: approximate exp(beta * x . y) for unit vectors
    by exp(beta * cos(pi * m / L));  returns [N, M] float32.  beta is the
    PV-DBOW training temperature (see PVDBOWConfig.temperature)."""
    m = hamming_distance(a_packed, b_packed).astype(jnp.float32)
    return jnp.exp(temperature * jnp.cos(jnp.pi * m / bits))


def asymmetric_cosine(
    query_vec: jax.Array,     # [dim] real-valued, any norm
    db_packed: jax.Array,     # [M, W] uint32 signatures
    planes: jax.Array,        # [bits, dim]
    bits: int,
) -> jax.Array:
    """Asymmetric LSH scoring (beyond-paper; index unchanged, noise ~1/2).

    E[(2 b_i(s) - 1) * r_i] = sqrt(2/pi) * s for unit s and Gaussian
    hyperplanes r_i, so

        cos(q, s) ~= sum_i (2 b_i(s) - 1) * (r_i . q_hat) / (L sqrt(2/pi))

    quantizes only the *stored* side; the query keeps its real
    projections.  Returns [M] estimated cosines (clipped to [-1, 1])."""
    q = query_vec / jnp.maximum(jnp.linalg.norm(query_vec), 1e-9)
    proj = planes @ q                                 # [bits]
    db_bits = unpack_bits(db_packed, bits).astype(jnp.float32)  # [M, bits]
    signs = 2.0 * db_bits - 1.0
    scale = 1.0 / (bits * jnp.sqrt(2.0 / jnp.pi))
    return jnp.clip(signs @ proj * scale, -1.0, 1.0)


# ---------------------------------------------------------------------------
# pure-numpy signing / distance for the serving hot path
#
# The semantic query cache (runtime/qcache) signs every incoming query
# vector per batch to form its key.  Operands are tiny ([B, dim] with B
# in the tens), where jax dispatch overhead dominates the actual math —
# so the cache keys on a numpy replica of the jax signing convention:
# bit j of word k is signature bit 32*k + j, identical to
# ``pack_bits(signature_bits(x, planes))`` (numpy's little-endian
# ``packbits`` + a uint32 view reproduces the in-lane layout on the
# little-endian machines everything here runs on).
# ---------------------------------------------------------------------------

_POPCOUNT8 = np.array([bin(i).count("1") for i in range(256)], np.uint8)


def sign_vectors_np(vecs: np.ndarray, planes) -> np.ndarray:
    """[B, dim] float -> [B, bits//32] uint32 packed signatures, pure
    numpy, bit-identical to ``pack_bits(signature_bits(vecs, planes))``."""
    vecs = np.atleast_2d(np.asarray(vecs, np.float32))
    planes_np = np.asarray(planes, np.float32)
    bits = (np.asarray(vecs @ planes_np.T) >= 0)
    packed = np.packbits(bits, axis=1, bitorder="little")
    return np.ascontiguousarray(packed).view(np.uint32)


def packed_hamming_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[N, W] x [M, W] packed uint32 -> [N, M] int32 Hamming distances
    (XOR + uint8-LUT popcount), pure numpy."""
    a2 = np.atleast_2d(np.asarray(a, np.uint32))
    b2 = np.atleast_2d(np.asarray(b, np.uint32))
    x = np.bitwise_xor(a2[:, None, :], b2[None, :, :])
    per_byte = _POPCOUNT8[np.ascontiguousarray(x).view(np.uint8)]
    return per_byte.reshape(a2.shape[0], b2.shape[0], -1).sum(
        axis=-1, dtype=np.int32)


class LSHIndex(NamedTuple):
    """Packed signatures + the hyperplanes that produced them."""
    packed: jax.Array      # [N, bits//32] uint32
    planes: jax.Array      # [bits, dim] float32
    bits: int

    @staticmethod
    def build(x: jax.Array, cfg: LSHConfig) -> "LSHIndex":
        planes = hyperplanes(cfg, x.shape[-1])
        return LSHIndex(pack_bits(signature_bits(x, planes)), planes, cfg.bits)

    def sign(self, x: jax.Array) -> jax.Array:
        """Signature for new vectors under the same hyperplanes."""
        if x.ndim == 1:
            x = x[None, :]
        return pack_bits(signature_bits(x, self.planes))

    def similarities(self, query_vec: jax.Array, use_kernel: bool = False,
                     temperature: float = 1.0) -> jax.Array:
        """exp-cosine similarity of ``query_vec`` to every indexed item."""
        q = self.sign(query_vec)
        if use_kernel:
            from repro.kernels.common import word_major
            from repro.kernels.hamming import ops as hamming_ops
            return hamming_ops.hamming_similarity(
                q, word_major(self.packed), self.bits,
                temperature=temperature)[0]
        return hamming_similarity(q, self.packed, self.bits, temperature)[0]
