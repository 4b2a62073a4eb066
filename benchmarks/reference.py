"""The plain reference and the comparison that decides ``correct``.

It imports nothing of the program.  The harness hands it plain data:
what it submitted where, the acks it got, and what each validator's
ledger says once the window has closed and the cluster has drained.
The reference works out from the submissions alone what the ledgers
have to say, under the guarantees the configuration's file states,
and every number it compares has the limit 0: the comparison is exact.

Served path (the ledger cannot be predicted epoch by epoch, since the
asynchronous schedule decides which epoch takes which transaction, so
the reference is the set of answers that the guarantees allow):

  lost        OK-acked transactions that never settled, beyond the
              evictions the mempools counted
  duplicated  transactions settled more than once
  foreign     settled transactions that nobody submitted
  misplaced   settled in the contribution of another validator than
              the one that admitted them
  forked      (validator, epoch) batches whose bytes differ from the
              first validator's
  unsettled   ordered epochs not yet settled at drain, worst validator
  oversize    batches with more transactions than the batch size

Lockstep path (benign synchronous schedule, so the ledger is fully
determined and the reference predicts it):

  wrong_batches   epochs whose committed batch is not, byte for byte,
                  the proposers' submitted transactions in
                  sorted-proposer order, first occurrence kept
  wrong_txs       transactions of those batches that differ
  wrong_rounds    epochs whose BBA round count is not what the
                  threshold coin gives: the reference interpolates the
                  coin key from f+1 dealt shares, checks it against
                  the dealt public key, and evaluates the coin (a VUF
                  of epoch, proposer and round) with Python's pow
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

Numbers = Dict[str, Tuple[float, float]]  # name -> (value, limit)


def _digest(txs: Sequence[bytes]) -> bytes:
    return hashlib.sha256(b"".join(txs)).digest()


def _flatten(contributions: Dict[str, List[bytes]]) -> List[bytes]:
    out: List[bytes] = []
    for proposer in sorted(contributions):
        out.extend(contributions[proposer])
    return out


def compare_served(obs: Dict) -> Numbers:
    """``obs``: node_ids, submissions [(tx, node_id, acked_ok)],
    ledgers {node_id: [ {proposer: [tx]} per epoch ]}, evicted,
    ordered / settled {node_id: frontier}, batch_size."""
    ids = list(obs["node_ids"])
    admitted_at: Dict[bytes, str] = {}
    for tx, node_id, ok in obs["submissions"]:
        if ok:
            admitted_at[tx] = node_id
    first = obs["ledgers"][ids[0]]
    settled_in: Dict[bytes, int] = {}
    duplicated = foreign = misplaced = oversize = 0
    for epoch, contributions in enumerate(first):
        size = 0
        for proposer, txs in contributions.items():
            size += len(txs)
            for tx in txs:
                if tx in settled_in:
                    duplicated += 1
                    continue
                settled_in[tx] = epoch
                home = admitted_at.get(tx)
                if home is None:
                    foreign += 1
                elif home != proposer:
                    misplaced += 1
        if size > obs["batch_size"]:
            oversize += 1
    unsettled_ok = sum(1 for tx in admitted_at if tx not in settled_in)
    lost = max(0, unsettled_ok - int(obs["evicted"]))
    want = [_digest(_flatten(c)) for c in first]
    forked = 0
    for nid in ids[1:]:
        ledger = obs["ledgers"][nid]
        forked += abs(len(ledger) - len(want))
        for epoch, contributions in enumerate(ledger[: len(want)]):
            if _digest(_flatten(contributions)) != want[epoch]:
                forked += 1
    unsettled = max(
        max(0, obs["ordered"][nid] - obs["settled"][nid]) for nid in ids
    )
    return {
        "lost": (lost, 0),
        "duplicated": (duplicated, 0),
        "foreign": (foreign, 0),
        "misplaced": (misplaced, 0),
        "forked": (forked, 0),
        "unsettled": (unsettled, 0),
        "oversize": (oversize, 0),
    }


def settled_epochs(obs: Dict) -> Dict[bytes, int]:
    """tx -> the epoch the first validator settled it in (first
    occurrence): what the latencies are read against."""
    out: Dict[bytes, int] = {}
    for epoch, contributions in enumerate(obs["ledgers"][obs["node_ids"][0]]):
        for txs in contributions.values():
            for tx in txs:
                out.setdefault(tx, epoch)
    return out


# -- lockstep ---------------------------------------------------------------


def predict_batch(
    per_node: Dict[str, List[bytes]]
) -> Dict[str, List[bytes]]:
    """HoneyBadger's commit rule on one benign epoch: every proposal
    is delivered and decrypted; proposers in sorted order contribute
    what they proposed, less what an earlier proposer already has."""
    seen = set()
    out: Dict[str, List[bytes]] = {}
    for proposer in sorted(per_node):
        mine = []
        for tx in per_node[proposer]:
            if tx not in seen:
                seen.add(tx)
                mine.append(tx)
        if mine:
            out[proposer] = mine
    return out


def _hash_to_int(*parts: bytes) -> int:
    h = hashlib.sha256(
        b"".join(len(p).to_bytes(4, "big") + p for p in parts)
    )
    return int.from_bytes(h.digest(), "big")


def _lagrange_at_zero(xs: Sequence[int], q: int) -> List[int]:
    lams = []
    for i, xi in enumerate(xs):
        num = den = 1
        for j, xj in enumerate(xs):
            if i != j:
                num = num * (-xj) % q
                den = den * (xi - xj) % q
        lams.append(num * pow(den, -1, q) % q)
    return lams


class CoinReference:
    """The threshold common coin as ops/coin.py's docstring specifies
    it, in Python ints: value = H2G("coin|" + id) ^ s, bit = lowest
    bit of sha256("coinval|" + id + value)."""

    def __init__(self, group: Dict[str, int], threshold: int,
                 shares: Sequence[Tuple[int, int]], master_pub: int) -> None:
        self.p, self.q, self.g = group["p"], group["q"], group["g"]
        use = sorted(shares)[:threshold]
        if len(use) < threshold:
            raise ValueError("fewer coin shares than the threshold")
        lams = _lagrange_at_zero([x for x, _ in use], self.q)
        self.secret = sum(l * v for l, (_, v) in zip(lams, use)) % self.q
        # the dealt key has to be the one these shares interpolate to
        self.key_ok = pow(self.g, self.secret, self.p) == master_pub
        self._nbytes = (self.p.bit_length() + 7) // 8

    def toss(self, coin_id: bytes) -> bool:
        x = _hash_to_int(b"h2g", b"coin|" + coin_id) % self.p
        base = pow(x or 1, 2, self.p)
        val = pow(base, self.secret, self.p)
        digest = hashlib.sha256(
            b"coinval|" + coin_id + val.to_bytes(self._nbytes, "big")
        ).digest()
        return bool(digest[-1] & 1)

    def rounds(self, epoch: int, ids: Sequence[str], cap: int = 64) -> int:
        """BBA rounds of one benign epoch: every instance has input 1
        and decides in the first round whose coin tosses 1."""
        worst = 0
        for nid in ids:
            rnd = 0
            while rnd < cap and not self.toss(
                b"%d|%s|%d" % (epoch, nid.encode(), rnd)
            ):
                rnd += 1
            worst = max(worst, rnd + 1)
        return worst


def compare_lockstep(obs: Dict) -> Numbers:
    """``obs``: node_ids, epochs [ {submitted {node_id: [tx]},
    committed {proposer: [tx]} | None, bba_rounds, epoch} ], coin
    {group, threshold, shares, master_pub}."""
    coin = CoinReference(**obs["coin"])
    wrong_batches = wrong_txs = wrong_rounds = 0
    for row in obs["epochs"]:
        want = predict_batch(row["submitted"])
        got = row["committed"]
        if got is None:
            wrong_batches += 1
            wrong_txs += sum(len(v) for v in want.values())
        elif got != want:
            wrong_batches += 1
            a, b = _flatten(want), _flatten(got)
            wrong_txs += abs(len(a) - len(b)) + sum(
                1 for x, y in zip(a, b) if x != y
            )
        if coin.rounds(row["epoch"], obs["node_ids"]) != row["bba_rounds"]:
            wrong_rounds += 1
    return {
        "wrong_batches": (wrong_batches, 0),
        "wrong_txs": (wrong_txs, 0),
        "wrong_rounds": (wrong_rounds, 0),
        "coin_key_mismatch": (0 if coin.key_ok else 1, 0),
    }


def verdict(numbers: Numbers) -> bool:
    return all(value <= limit for value, limit in numbers.values())


__all__ = ["compare_served", "compare_lockstep", "settled_epochs",
           "predict_batch", "CoinReference", "verdict", "Numbers"]
