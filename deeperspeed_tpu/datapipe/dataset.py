"""Streaming memory-mapped token-shard dataset + deterministic ordering.

``TokenShardDataset`` indexes fixed ``seq_len + 1``-token windows over a
memory-mapped token corpus — a single ``.npy`` file of token ids (the
path the config names) or a directory of ``*.npy`` shards. Nothing
is read until a window is fetched, so a multi-TB corpus costs a few
mmap handles, and the page cache does the streaming.

Epoch order is a **counter-based** permutation: ``epoch_order(seed,
epoch, n)`` derives the whole epoch's order from the Philox counter RNG
keyed by ``(seed, epoch)``. There is no mutable RNG object whose state
must be serialized — any ``(seed, epoch, cursor)`` triple reconstructs
the exact remaining sample sequence, which is what makes mid-epoch
resume bit-identical. ``order_fingerprint`` condenses the order into a
short hash the checkpoint carries so a resume against a changed corpus
or seed is detected instead of silently replaying different data.
"""

import hashlib
import os
from typing import List, Optional

import numpy as np

__all__ = [
    "TokenShardDataset",
    "epoch_order",
    "order_fingerprint",
]


def _load_shard(path: str):
    arr = np.load(path, mmap_mode="r")
    if arr.ndim != 1:
        raise ValueError(
            f"token shard {path} must be a 1-D token array, got shape "
            f"{arr.shape}")
    return arr


class TokenShardDataset:
    """Indexable windows of ``seq_len + 1`` tokens over mmap'd shards.

    Windows never straddle a shard boundary (each shard's ragged tail is
    dropped), so shard files can be produced independently and
    concatenated logically in sorted-filename order — the order is part
    of the deterministic-iteration contract.
    """

    def __init__(self, source, seq_len: int, dtype=np.int32):
        self.seq_len = int(seq_len)
        if self.seq_len < 1:
            raise ValueError(f"seq_len must be >= 1, got {seq_len}")
        self.dtype = np.dtype(dtype)
        self._window = self.seq_len + 1
        if isinstance(source, np.ndarray):
            shards: List[np.ndarray] = [source]
            self.paths = ["<in-memory>"]
        else:
            source = str(source)
            if os.path.isdir(source):
                self.paths = sorted(
                    os.path.join(source, f) for f in os.listdir(source)
                    if f.endswith(".npy"))
                if not self.paths:
                    raise FileNotFoundError(
                        f"no .npy token shards in directory {source}")
            elif os.path.isfile(source):
                self.paths = [source]
            else:
                raise FileNotFoundError(f"token source {source} not found")
            shards = [_load_shard(p) for p in self.paths]
        self._shards = shards
        per_shard = [s.size // self._window for s in shards]
        if sum(per_shard) == 0:
            raise ValueError(
                f"token source holds no full window of {self._window} "
                f"tokens (sizes: {[s.size for s in shards]})")
        # windows[i] lives in shard bisect(cum, i); cum is exclusive
        self._cum = np.cumsum([0] + per_shard)
        self._len = int(self._cum[-1])

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, i: int) -> np.ndarray:
        i = int(i)
        if not 0 <= i < self._len:
            raise IndexError(f"window {i} out of range [0, {self._len})")
        s = int(np.searchsorted(self._cum, i, side="right")) - 1
        local = i - int(self._cum[s])
        w = self._window
        chunk = self._shards[s][local * w:(local + 1) * w]
        return np.asarray(chunk, dtype=self.dtype)

    def identity(self) -> dict:
        """What the checkpoint fingerprint binds to: the shard layout."""
        return {
            "n_windows": self._len,
            "seq_len": self.seq_len,
            "shards": [os.path.basename(p) for p in self.paths],
        }


def epoch_order(seed: int, epoch: int, n: int,
                shuffle: bool = True) -> np.ndarray:
    """The epoch's sample order — a pure function of (seed, epoch, n).

    Philox is a counter-based generator: keying it with (seed, epoch)
    gives independent streams per epoch with nothing to carry between
    them, so the permutation can be recomputed identically at resume
    from just the integers in the checkpoint.
    """
    if not shuffle:
        return np.arange(n, dtype=np.int64)
    key = (int(seed) & (2**64 - 1)) << 64 | (int(epoch) & (2**64 - 1))
    rng = np.random.Generator(np.random.Philox(key=key))
    return rng.permutation(n).astype(np.int64)


def order_fingerprint(seed: int, epoch: int, n: int,
                      shuffle: bool = True,
                      identity: Optional[dict] = None) -> str:
    """Short stable hash naming the epoch order (plus the dataset
    identity) for the resume sanity check. The order is a pure function
    of ``(seed, epoch, n, shuffle)``, so hashing those parameters binds
    the fingerprint to the order exactly — without materializing the
    O(n) permutation, which matters on billion-window corpora."""
    h = hashlib.sha256()
    h.update(
        f"{int(seed)}:{int(epoch)}:{int(n)}:{int(bool(shuffle))}".encode())
    if identity:
        h.update(repr(sorted(identity.items())).encode())
    return h.hexdigest()[:16]
