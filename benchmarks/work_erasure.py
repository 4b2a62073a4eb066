"""What the Reed-Solomon programs' work costs, reckoned from the widths
alone, so that a roofline share reads the same work whatever implements
it (bit planes on the MXU, log/antilog tables, a carry-less multiply).

A GF(2^8) matrix of m rows by k columns, applied to a (k, L) byte
matrix as a 0/1 matrix over bits, is an (8m x 8k) by (8k x L) product:
2 * (8m) * (8k) operations (multiply, add) a byte column, and a column
holds k input bytes, so **128 * m operations an input byte**:

    encode            m = n - k   (the parity rows; the data rows are copied)
    decode            m = k       (the inverse of the k surviving rows)
    decode + recheck  m = n       (decode, then the parity again: k + n - k)

against the bf16 peak (the products are exact in any precision that
holds 8k <= 2,048 ones; bf16 is the one the chip publishes a peak for
that the program's bit planes use).

Bytes, an input byte (k * L of them a matrix):

    encode            1 read + n/k written (the whole shard set)
    decode            1 read + 1 written
    decode + recheck  1 read (the k shards) + 1 written (the data) + n/k
                      read once more (the n re-encoded shards, hashed to
                      the Merkle roots), and 32 bytes of root a matrix

against the HBM bandwidth.  The larger of the two times is the least the
chip could take (benchmarks/work.py::roofline_pct's rule).

SHA-256 is 32-bit adds, rotates and logic on the vector unit, for which
this chip publishes no peak: the forest inside the fused program is
counted in bytes only (each hashed byte read once).  So
``decode_recheck_roofline`` understates the least time by whatever the
hashing's arithmetic needs, and a low share there is first of all the
hash's sequential 64-byte blocks, not the matrix product.

The input bytes and the item counts come from the placement tally's
``device_bytes`` / ``device_items`` (cleisthenes_tpu/ops/placement.py),
which count a batch as it was handed over, before the program pads its
batch axis to a power of two: padding is the program's own cost, not
work.  A tally without byte fields (an older program) gives None.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

ROOT_BYTES = 32

# placement-tally families of the GF(2^8) coder, by what a program does
ENCODE = ("rs_gf256.encode", "rs_gf256.encode_batch")
DECODE = ("rs_gf256.decode", "rs_gf256.decode_batch")
DECODE_RECHECK = ("rs_gf256.decode_recheck",)


def _device(delta: Dict[str, Dict[str, int]], families: Sequence[str]):
    """(input bytes, items) the families sent to the device, or None
    where a row that ran there has no byte count."""
    nbytes = items = 0
    for fam in families:
        row = delta.get(fam)
        if not row or not row.get("device_calls", 0):
            continue
        if "device_bytes" not in row:
            return None
        nbytes += int(row["device_bytes"])
        items += int(row["device_items"])
    return nbytes, items


def _work(in_bytes: int, rows: int, moved_rows: int, k: int, extra: int = 0):
    """``rows`` of the matrix applied; ``moved_rows`` rows of L bytes
    read and written for every k rows of input."""
    return {
        "in_bytes": in_bytes,
        "bf16_flops": 128 * rows * in_bytes,
        "bytes": in_bytes * moved_rows // k + extra,
    }


def rs256_work(delta: Dict, n: int, k: int) -> Optional[Dict]:
    """Operations and bytes of the RS-only device batches (encodes and
    decodes) in a placement delta."""
    enc, dec = _device(delta, ENCODE), _device(delta, DECODE)
    if enc is None or dec is None:
        return None
    e = _work(enc[0], n - k, k + n, k)
    d = _work(dec[0], k, k + k, k)
    return {key: e[key] + d[key] for key in e}


def decode_recheck_work(delta: Dict, n: int, k: int) -> Optional[Dict]:
    """Operations and bytes of the fused decode + re-encode + roots
    batches in a placement delta."""
    got = _device(delta, DECODE_RECHECK)
    if got is None:
        return None
    return _work(got[0], n, k + k + n, k, ROOT_BYTES * got[1])


def roofline_pct(work: Dict, device_seconds: float, peaks: Dict) -> float:
    """The least time the chip could take for ``work`` (the larger of
    operations over the bf16 peak and bytes over the bandwidth) as a
    share of the device time spent, in percent."""
    least = max(
        work["bf16_flops"] / peaks["bf16_flops_per_s"],
        work["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / device_seconds


__all__ = ["rs256_work", "decode_recheck_work", "roofline_pct", "ENCODE",
           "DECODE", "DECODE_RECHECK"]
