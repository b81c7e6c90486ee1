"""Traffic made from the seed: values, keys, arrivals, skew, percentiles.

Pure functions over numbers; nothing here touches the program or JAX.
Every seed gives the same SET of gaps, shards and key ranks in another
order, so that a seed changes the order of the work and not its amount.
"""
from __future__ import annotations

import math

import numpy as np

MASK56 = (1 << 56) - 1
_MULT = 0x9E3779B97F4A7C15 & MASK56 | 1   # odd: a bijection mod 2**56
_MULT_INV = pow(_MULT, -1, 1 << 56)
_KEY_ALPHABET = "abcdefghijklmnopqrstuvwxyz0123456789"


def key_name(i: int) -> str:
    """Key number -> short key: a..z, 0..9, then two characters and up."""
    s = ""
    while True:
        s = _KEY_ALPHABET[i % 36] + s
        i = i // 36 - 1
        if i < 0:
            return s


class HexValues:
    """``digits`` hex digits per value; value number <-> value is a
    bijection salted by the seed, so every write of a run is its own."""

    def __init__(self, seed: int, digits: int = 14, **_):
        if digits != 14:
            raise ValueError("hex values are 14 digits (56 bits)")
        self.salt = int(np.random.default_rng(seed).integers(0, 1 << 56))

    def encode(self, vid: int) -> str:
        return f"{(self.salt + vid * _MULT) & MASK56:014x}"

    def decode(self, value) -> int | None:
        try:
            x = int(value, 16)
        except (TypeError, ValueError):
            return None
        if len(value) != 14:
            return None
        return ((x - self.salt) * _MULT_INV) & MASK56


class RecordValues:
    """YCSB-style record of ``fieldcount`` x ``fieldlength`` bytes as one
    value: 16 hex digits of the write's number, then filler cut from a
    pool drawn from the seed."""

    def __init__(self, seed: int, fieldcount: int = 10,
                 fieldlength: int = 100, **_):
        self.size = fieldcount * fieldlength
        rng = np.random.default_rng(seed)
        letters = np.frombuffer(
            b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)
        self.pool = letters[rng.integers(0, 52, 1 << 16)].tobytes().decode()
        self.span = len(self.pool) - self.size

    def encode(self, vid: int) -> str:
        off = (vid * 7919) % self.span
        return f"{vid:016x}" + self.pool[off:off + self.size - 16]

    def decode(self, value) -> int | None:
        try:
            vid = int(value[:16], 16)
        except (TypeError, ValueError):
            return None
        return vid if self.encode(vid) == value else None


def fnv64(data: bytes) -> int:
    """FNV-1a, 64 bits, over a key's bytes: the hash that partitions keys
    over shards."""
    h = 0xCBF29CE484222325
    for b in data:
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def fnvhash64(vals) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64(long)``: FNV over the long's eight octets,
    low octet first, in Java's wrapping 64-bit arithmetic, then
    ``Math.abs``.  Takes and gives whole arrays."""
    v = np.asarray(vals, np.int64).astype(np.uint64)
    h = np.full(v.shape, 0xCBF29CE484222325, np.uint64)
    prime = np.uint64(1099511628211)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * prime
            v = v >> np.uint64(8)
    return np.abs(h.astype(np.int64))


def poisson_schedule(rate: float, total_s: float, n_shards: int,
                     seed: int):
    """Open-loop arrivals at ``rate``/s for ``total_s``: offsets (s) and
    shards.  The gaps are the exponential distribution's own quantiles
    and the shards an equal share each; the seed only shuffles both."""
    n = int(round(rate * total_s))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    shards = 1 + (np.arange(n) % n_shards)
    rng.shuffle(shards)
    return np.cumsum(gaps), shards


# YCSB's ScrambledZipfianGenerator: a zipfian over ITEM_COUNT items with
# the constant 0.99 and its zeta precomputed, then hashed onto the records
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302
YCSB_ZIPFIAN_CONSTANT = 0.99


def ycsb_zipfian(u: np.ndarray, items: int = YCSB_ITEM_COUNT + 1,
                 theta: float = YCSB_ZIPFIAN_CONSTANT,
                 zetan: float = YCSB_ZETAN) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` (Gray et al.'s method) for
    uniform draws ``u``: item numbers from 0, the hottest first."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def scrambled_zipfian(recordcount: int, n: int, seed: int) -> np.ndarray:
    """``n`` record numbers as YCSB's ``ScrambledZipfianGenerator`` chooses
    them: ``fnvhash64(zipfian over ITEM_COUNT) mod recordcount``.  The
    uniform draws are the unit interval's own quantiles and the seed
    shuffles them: every seed asks for the same records as often."""
    u = (np.arange(n) + 0.5) / n
    recs = fnvhash64(ycsb_zipfian(u)) % recordcount
    np.random.default_rng(seed).shuffle(recs)
    return recs


def ycsb_key_names(recordcount: int) -> list:
    """``CoreWorkload.buildKeyName`` with ``insertorder=hashed`` (YCSB's
    default): record n is the key ``user<fnvhash64(n)>``."""
    return [f"user{h}" for h in fnvhash64(np.arange(recordcount)).tolist()]


def percentile(values, q: float, n_missing: int = 0, missing=math.inf):
    """Nearest-rank percentile of ``values`` with ``n_missing`` more
    samples that sit above every one of them.  None on an empty sample."""
    n = len(values) + n_missing
    if n == 0:
        return None
    k = max(0, math.ceil(q / 100.0 * n) - 1)
    if k >= len(values):
        return missing
    return float(np.partition(np.asarray(values, np.float64), k)[k])
