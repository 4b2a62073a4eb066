"""The one general traffic generator: every cell's traffic file is its
parameters (benchmarks/traffic/<cell>.json).

Copied in shape from tools/loadgen.py::build_schedule (Pareto gaps
alpha=1.5 between arrivals, Pareto fees alpha=1.2 capped at 10^6,
client ids cycling a population), because the yardstick may not live
in a file a later PR can change.  Two things differ on purpose:

- loadgen lays the arrivals on ticks; here they are due times in
  seconds on the wall clock, scaled so that the whole window holds
  exactly ``rate * seconds`` of them.
- the gaps and fees are one fixed multiset per traffic file
  (``shape_seed``); ``--seed`` only shuffles their order and makes the
  transaction bytes.  Every seed then offers the same work, in another
  order, so seeds differ no more than two runs of one seed do.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

FEE_CAP = 1_000_000
KIND_TIMED = b"t"
KIND_WARM = b"w"


@dataclasses.dataclass
class Arrival:
    """One client transaction as the generator hands it over."""

    client: str
    nonce: int
    fee: int
    tx: bytes


def _pareto(rng: np.random.Generator, alpha: float, count: int) -> np.ndarray:
    # numpy's pareto is the Lomax form; +1 gives random.paretovariate's
    return rng.pareto(alpha, count) + 1.0


class TxSource:
    """Unique ``tx_bytes``-wide transactions and their fees from the
    seed, handed out in order.  Each transaction starts with a kind
    byte and a 7-byte counter, so no two are equal and a warm-up
    transaction can never be mistaken for a timed one."""

    BLOCK = 65_536

    def __init__(self, traffic: Dict, tx_bytes: int, seed: int,
                 kind: bytes = KIND_TIMED, fee_count: int = 0) -> None:
        if tx_bytes < 16:
            raise ValueError("tx_bytes must leave room for the 8-byte tag")
        self._tx_bytes = tx_bytes
        self._kind = kind
        self._rng = np.random.default_rng([seed, kind[0]])
        self._population = int(traffic["population"])
        # one fixed multiset of fees, ``fee_count`` of them where the
        # loop knows its length, handed out in the seed's order and
        # cycled where the loop runs on
        fees = _pareto(
            np.random.default_rng(int(traffic["shape_seed"]) + 1),
            float(traffic["fee_alpha"]),
            fee_count or self.BLOCK,
        )
        fees = np.minimum(FEE_CAP, fees).astype(np.int64)
        self._fees = self._rng.permutation(fees)
        self._next = 0
        self._pool: List[bytes] = []

    def _refill(self) -> None:
        raw = self._rng.bytes(self.BLOCK * self._tx_bytes)
        w = self._tx_bytes
        self._pool = [raw[i * w:(i + 1) * w] for i in range(self.BLOCK)]
        self._pool.reverse()

    def take(self, count: int) -> List[Arrival]:
        out = []
        for _ in range(count):
            if not self._pool:
                self._refill()
            i = self._next
            self._next += 1
            body = self._pool.pop()
            out.append(
                Arrival(
                    client=f"c{i % self._population:07d}",
                    nonce=i,
                    fee=int(self._fees[i % len(self._fees)]),
                    tx=self._kind + i.to_bytes(7, "big") + body[8:],
                )
            )
        return out


def open_loop_schedule(
    traffic: Dict, tx_bytes: int, seed: int, seconds: float
) -> Tuple[np.ndarray, List[Arrival]]:
    """(due times in seconds from the window's start, arrivals) for an
    open loop: ``rate_tx_per_s * seconds`` arrivals, the last one due
    exactly as the window closes."""
    count = max(1, int(round(float(traffic["rate_tx_per_s"]) * seconds)))
    gaps = _pareto(
        np.random.default_rng(int(traffic["shape_seed"])),
        float(traffic["arrival_alpha"]),
        count,
    )
    gaps = np.random.default_rng([seed, 0]).permutation(gaps)
    due = np.cumsum(gaps)
    due *= seconds / due[-1]
    source = TxSource(traffic, tx_bytes, seed, fee_count=count)
    return due, source.take(count)


__all__ = ["Arrival", "TxSource", "open_loop_schedule", "KIND_TIMED",
           "KIND_WARM"]
