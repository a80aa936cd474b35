"""Shared helpers for the kernel ops wrappers.

Every kernel's public wrapper needs the same things: one decision on
whether Pallas bodies compile for the TPU or run in the interpreter,
row padding to tile multiples so kernels never see ragged blocks, the
word-major signature layout the scoring kernels read, and the segment
layout the banded segment sum reads.  One copy here keeps the wrappers
in sync.
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_log = logging.getLogger(__name__)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when JAX
    finds no TPU.  The first interpreted call in a process says so on
    the log, so a run that expected the chip cannot pass for one."""
    if on_tpu():
        return False
    _warn_interpreted(jax.default_backend())
    return True


@functools.cache
def _warn_interpreted(backend: str) -> None:
    _log.warning("Pallas kernels run in interpret mode: JAX backend is %r, "
                 "not a TPU", backend)


def pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad axis 0 of ``x`` up to a multiple of ``multiple``."""
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


# Row alignment of every word-major signature set: a multiple of each
# scoring tile (all powers of two up to 512), so no wrapper re-pads.
SIG_ALIGN = 512


class WordMajorSigs(NamedTuple):
    """Packed LSH signatures in the layout every signature-scoring
    kernel reads.  ``t`` is word-major [W, M'] uint32: with row-major
    [M, W] the W (8 for 256 bits) words would sit on the 128-lane axis,
    padded 16x in HBM, and every DMA would be unaligned.  M' is the row
    count ``n`` zero-padded to a multiple of ``SIG_ALIGN``.  Built once
    per signature set by ``word_major`` and kept on the device."""
    t: jax.Array
    n: int


def word_major(db_packed) -> WordMajorSigs:
    """[M, W] packed signatures -> ``WordMajorSigs``: the one place the
    kernels' signature layout is made."""
    db = jnp.asarray(db_packed, jnp.uint32)
    return WordMajorSigs(pad_rows(db, SIG_ALIGN).T, int(db.shape[0]))


def sig_tile(db: WordMajorSigs, tm: int) -> int:
    """The row tile for ``db``: ``tm`` capped at its padded width, which
    the tile must divide."""
    mp = int(db.t.shape[1])
    tm = min(int(tm), mp)
    if mp % tm:
        raise ValueError(f"row tile {tm} does not divide the "
                         f"{mp} padded signature rows")
    return tm


LANES = 128


def slot_pad(n_segments: int) -> int:
    """Segment slots the segment-sum kernels reduce into: the count
    rounded up to a lane multiple."""
    return max(LANES, -(-int(n_segments) // LANES) * LANES)


class SegmentLayout(NamedTuple):
    """A doc -> segment-slot map in the layout the banded segment-sum
    kernel reads, made once per slot map by ``segment_layout``.

    ``seg`` is the [1, M'] int32 slot row over the padded signature
    rows, padding docs at the out-of-range slot ``s_pad`` (the slot
    count rounded up to a lane multiple).  Row tile j (``tm`` rows;
    tm = M' / len(starts)) reduces into the window of ``width`` slots
    from ``starts[j]``: every start a multiple of 128, every window
    inside ``[0, s_pad)`` and holding all of its tile's real slots.
    Segment-sorted docs give a narrow band; any other order gives
    ``width == s_pad``, every start 0: the full one-hot."""
    seg: jax.Array
    starts: jax.Array
    width: int
    s_pad: int


def segment_layout(seg_ids, n_segments: int, db: WordMajorSigs,
                   tm: int = 256) -> SegmentLayout:
    """``SegmentLayout`` of the slot map ``seg_ids`` (one int per row
    of ``db``) over ``db``'s padded rows, cut into tiles of
    ``sig_tile(db, tm)``.  Each tile's window starts at its lowest real
    slot rounded down to a lane multiple; ``width`` is the least lane
    multiple that reaches every tile's highest real slot from there,
    capped at ``s_pad``, and starts are then clamped to ``s_pad -
    width``.  Host numpy, one pass over the map."""
    ids = np.asarray(seg_ids, np.int32).ravel()
    if ids.size != db.n:
        raise ValueError(f"{ids.size} slots for {db.n} signature rows")
    s_pad = slot_pad(n_segments)
    seg = np.full(int(db.t.shape[1]), s_pad, np.int32)
    seg[:ids.size] = ids
    tiles = seg.reshape(-1, sig_tile(db, tm))
    real = tiles < s_pad
    lo = np.where(real, tiles, s_pad).min(axis=1)
    hi = np.where(real, tiles, -1).max(axis=1)
    base = np.where(real.any(axis=1), lo // LANES * LANES, 0)
    reach = int((hi - base + 1).max())
    width = min(s_pad, max(LANES, -(-reach // LANES) * LANES))
    starts = np.minimum(base, s_pad - width).astype(np.int32)
    return SegmentLayout(jnp.asarray(seg[None, :]), jnp.asarray(starts),
                         width, s_pad)


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()
