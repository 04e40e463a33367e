"""SeaHash, pure Python: the 64-bit hash the metric engine's RFC names
for series ids (TSID = SeaHash(canonical series key) & i63).  A copy of
the spec twin in horaedb_tpu/common/seahash.py, kept here so that the
yardstick maps a response's tsids to hosts without importing the
program."""

from __future__ import annotations

_MASK = (1 << 64) - 1
_K = 0x6EED_0E9D_A4D9_4A4F
_SEEDS = (0x16F1_1FE8_9B0D_677C, 0xB480_A793_D8E6_C86C,
          0x6FE2_E5AA_F078_EBC9, 0x14F9_94A4_C525_9381)


def _diffuse(x: int) -> int:
    x = (x * _K) & _MASK
    x ^= (x >> 32) >> (x >> 60)
    return (x * _K) & _MASK


def hash64(buf: bytes) -> int:
    lanes = list(_SEEDS)
    for lane, i in enumerate(range(0, len(buf), 8)):
        k = lane % 4
        lanes[k] = _diffuse(
            lanes[k] ^ int.from_bytes(buf[i:i + 8], "little"))
    a, b, c, d = lanes
    return _diffuse(a ^ b ^ c ^ d ^ len(buf))


def tsid_of(metric: str, labels: dict) -> str:
    """The tsid of a series, as the server prints it: the hash of the
    metric name and the sorted `k=v` pairs, 63 bits."""
    pairs = ",".join(sorted(f"{k}={v}" for k, v in labels.items()))
    return str(hash64(f"{metric}{{{pairs}}}".encode())
               & ((1 << 63) - 1))
