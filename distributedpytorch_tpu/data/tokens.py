"""Token sources of the ``tokens`` task: random-access datasets whose sample
is ``{"tokens": int32 (seq_len,)}`` — input and (shifted) target at once.

* :class:`SyntheticTokens` — seeded uniform ids: sample ``i`` is
  ``default_rng((seed, i))``'s draw, the same under any loader sharding;
* :class:`PackedTokens` — a flat little-endian ``uint32`` file of ids, as a
  tokenizer's packing pass writes it (documents joined end to end),
  memory-mapped and cut into ``seq_len`` windows; :func:`write_token_file`
  writes one.
"""

from __future__ import annotations

import os

import numpy as np

TOKEN_DTYPE = np.dtype("<u4")


class SyntheticTokens:
    def __init__(self, n_samples: int, seq_len: int, vocab_size: int,
                 seed: int = 0):
        if n_samples < 1 or seq_len < 3:
            raise ValueError(
                f"synthetic token source needs n_samples >= 1 and seq_len "
                f">= 3 (got {n_samples}, {seq_len})")
        self.n_samples, self.seq_len = int(n_samples), int(seq_len)
        self.vocab_size, self.seed = int(vocab_size), int(seed)

    def __len__(self) -> int:
        return self.n_samples

    def __getitem__(self, index: int, rng=None) -> dict:
        del rng  # the sample is fixed by (seed, index), not by the epoch
        draw = np.random.default_rng((self.seed, int(index)))
        return {"tokens": draw.integers(0, self.vocab_size, self.seq_len,
                                        dtype=np.int32)}

    def __str__(self) -> str:
        return (f"SyntheticTokens(n={self.n_samples},seq_len={self.seq_len},"
                f"vocab={self.vocab_size},seed={self.seed})")


class PackedTokens:
    def __init__(self, path: str, seq_len: int,
                 vocab_size: int | None = None, first: int = 0,
                 count: int | None = None):
        """Windows ``first .. first + count`` of the file (``count=None``:
        to its end; a negative ``first`` counts from the end, as a val
        split taken off the tail)."""
        size = os.path.getsize(path)
        if size % TOKEN_DTYPE.itemsize:
            raise ValueError(
                f"{path}: {size} bytes is no whole number of uint32 ids")
        self.path, self.seq_len = path, int(seq_len)
        ids = np.memmap(path, dtype=TOKEN_DTYPE, mode="r")
        windows = len(ids) // self.seq_len
        first = max(0, windows + first) if first < 0 else min(first, windows)
        self.n_samples = windows - first if count is None \
            else min(int(count), windows - first)
        if self.n_samples < 1:
            raise ValueError(
                f"{path} holds {len(ids)} ids: no sequence of "
                f"data.seq_len={seq_len} in windows {first}..")
        self._ids = ids[first * self.seq_len:
                        (first + self.n_samples) * self.seq_len]
        self.vocab_size = vocab_size

    def __len__(self) -> int:
        return self.n_samples

    def __getitem__(self, index: int, rng=None) -> dict:
        del rng
        i = int(index)
        if not 0 <= i < self.n_samples:
            raise IndexError(i)
        ids = np.asarray(self._ids[i * self.seq_len:(i + 1) * self.seq_len])
        if self.vocab_size is not None and ids.size \
                and int(ids.max()) >= self.vocab_size:
            raise ValueError(
                f"{self.path}: sequence {i} holds id {int(ids.max())}, "
                f"outside the model's {self.vocab_size} vocabulary rows")
        return {"tokens": ids.astype(np.int32)}

    def __str__(self) -> str:
        return (f"PackedTokens({self.path},n={self.n_samples},"
                f"seq_len={self.seq_len})")


def write_token_file(path: str, tokens) -> str:
    """Write ids (any integer array, flattened) as the packed file."""
    np.asarray(tokens).astype(TOKEN_DTYPE).ravel().tofile(path)
    return path
