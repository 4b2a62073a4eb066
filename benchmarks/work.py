"""What one 256-bit modular exponentiation costs, reckoned from the
widths alone, so that the roofline share reads the same work whatever
implements it (a comb, a window, Montgomery limbs of any width).

One item = one modular exponentiation with a 256-bit modulus and a
256-bit exponent by plain square-and-multiply:

- 256 squarings and, with every exponent bit set, 256 multiplications:
  512 modular products.  (A random exponent sets half its bits; the
  plain algorithm's worst case is the yardstick, and a constant factor
  cannot push a share of a fraction of a percent past 100%.)
- one modular product by schoolbook on 8-bit digits is the 32 x 32
  digit product plus a Montgomery reduction of the same shape (32
  rounds of a 32-digit multiply-add): 2 * 32 * 32 digit
  multiply-accumulates, two operations each (multiply, add):
  4,096 int8 operations.

So 512 * 4,096 = 2,097,152 int8 operations an item.  A ``dual_pow``
item (u1^e1 * u2^e2) is two exponentiations; a comb item is one,
although the comb does fewer products.

Bytes: an item reads a 33-byte base and a 32-byte exponent and writes a
33-byte result as the kernels pack them (ops/modmath.py); the modulus
and Montgomery constants are shared by the whole batch.
"""

from __future__ import annotations

from typing import Dict

DIGIT_BITS = 8
MODULUS_BITS = 256
DIGITS = MODULUS_BITS // DIGIT_BITS  # 32
PRODUCTS_PER_EXP = 2 * MODULUS_BITS  # 256 squarings + 256 multiplications
OPS_PER_PRODUCT = 2 * (2 * DIGITS * DIGITS)  # product + reduction, mul + add
OPS_PER_EXP = PRODUCTS_PER_EXP * OPS_PER_PRODUCT  # 2,097,152
BYTES_PER_EXP = 33 + 32 + 33

# exponentiations in one item of each placement-tally op
EXPS_PER_ITEM = {"pow": 1, "comb": 1, "dual_pow": 2}
FAMILY = "modexp_12x22"


def modexp256_work(placement_delta: Dict[str, Dict[str, int]]) -> Dict:
    """Operations and bytes of the 256-bit exponentiation items that
    the placement tally says went to the device."""
    exps = 0
    items = 0
    for op, per_item in EXPS_PER_ITEM.items():
        row = placement_delta.get(f"{FAMILY}.{op}")
        if row:
            items += int(row.get("device_items", 0))
            exps += per_item * int(row.get("device_items", 0))
    return {
        "items": items,
        "exps": exps,
        "int8_ops": exps * OPS_PER_EXP,
        "bytes": exps * BYTES_PER_EXP,
    }


def roofline_pct(work: Dict, device_seconds: float, peaks: Dict) -> float:
    """The least time the chip could take for ``work`` (the larger of
    operations over the int8 peak and bytes over the bandwidth) as a
    share of the device time spent, in percent."""
    least = max(
        work["int8_ops"] / peaks["int8_ops_per_s"],
        work["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / device_seconds


__all__ = ["modexp256_work", "roofline_pct", "OPS_PER_EXP", "BYTES_PER_EXP",
           "EXPS_PER_ITEM"]
