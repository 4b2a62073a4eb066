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
              those that admitted them (a transaction's homes are every
              validator that acknowledged it OK: one, unless its client
              had to send it again after its validator was killed)
  forked      (validator, epoch) batches whose bytes differ from the
              first validator's
  unsettled   ordered epochs not yet settled at drain, worst validator
  oversize    batches with more transactions than the batch size

Under a fault schedule (validators killed inside the window, and as a
rule restarted from their logs) the same numbers hold across the
outage: a transaction that was sent again counts once; ``forked`` and
``unsettled`` are over every validator at rest, the restarted ones
included, so one that never catches up, or catches up to something
else, is ``forked`` (only a validator the schedule leaves down is left
out).  And one number more:

  killed_proposed  contributions of a killed validator in epochs that
                   were stamped ordered while it was down, beyond the
                   ``pipeline_depth`` epochs from its own ordered
                   frontier on that it may have had in flight when it
                   was killed: a dead validator that goes on proposing
                   means the kill did not take

A configuration with a write-ahead log states how durable a settled
batch is.  The harness hands over each validator's log and the length
of the prefix a crash would leave at that level (what the operating
system holds, or what the last sync left); the reference parses the
prefix itself and holds it to the ledgers (``compare_wal``):

  wal_short      OK-acked and settled transactions read back from the
                 prefix of fewer validators' logs than the
                 configuration's ``durable_replicas``: the guarantee as
                 a client would state it
  wal_missing    (validator, settled epoch) pairs with no whole batch
                 record of that epoch in the prefix
  wal_wrong      batch records that are not, proposer for proposer and
                 byte for byte, the batch that validator settled in
                 that epoch; records of epochs it never settled; epochs
                 out of order or twice
  wal_unordered  settled epochs whose ordered record is absent from
                 the prefix or follows their batch record; compared
                 where the configuration states that order
  wal_torn       bytes of the prefix after its last whole record (a
                 drained run leaves none)

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
import struct
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

Numbers = Dict[str, Tuple[float, float]]  # name -> (value, limit)


def _digest(txs: Sequence[bytes]) -> bytes:
    return hashlib.sha256(b"".join(txs)).digest()


def _flatten(contributions: Dict[str, List[bytes]]) -> List[bytes]:
    out: List[bytes] = []
    for proposer in sorted(contributions):
        out.extend(contributions[proposer])
    return out


def _at_rest(obs: Dict) -> List[str]:
    """The validators that are up once the system is at rest: all,
    but for those a fault schedule killed and did not restart."""
    down = (obs.get("faults") or {}).get("down_at_rest", ())
    return [nid for nid in obs["node_ids"] if nid not in down]


def witness(obs: Dict) -> str:
    """The validator whose ledger the others' are compared with, and
    the latencies read against: the first that is up at rest."""
    return _at_rest(obs)[0]


def compare_served(obs: Dict) -> Numbers:
    """``obs``: node_ids, submissions [(tx, node_id, acked_ok)],
    ledgers {node_id: [ {proposer: [tx]} per epoch ]}, evicted,
    ordered / settled {node_id: frontier}, batch_size; under a fault
    schedule also faults: pipeline_depth, down_at_rest [node_id],
    outages [{node, ordered_at_kill, ordered_epochs_at_restart (the
    epochs stamped ordered when it was restarted; absent if it never
    was)}]."""
    ids = _at_rest(obs)
    admitted_at: Dict[bytes, Tuple[str, ...]] = {}
    for tx, node_id, ok in obs["submissions"]:
        if ok:
            admitted_at[tx] = admitted_at.get(tx, ()) + (node_id,)
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
                homes = admitted_at.get(tx)
                if homes is None:
                    foreign += 1
                elif proposer not in homes:
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
    numbers = {
        "lost": (lost, 0),
        "duplicated": (duplicated, 0),
        "foreign": (foreign, 0),
        "misplaced": (misplaced, 0),
        "forked": (forked, 0),
        "unsettled": (unsettled, 0),
        "oversize": (oversize, 0),
    }
    faults = obs.get("faults")
    if faults is not None:
        killed_proposed = 0
        for outage in faults["outages"]:
            lo = outage["ordered_at_kill"] + faults["pipeline_depth"]
            hi = outage.get("ordered_epochs_at_restart", len(first))
            killed_proposed += sum(
                1 for contributions in first[lo:hi]
                if contributions.get(outage["node"])
            )
        numbers["killed_proposed"] = (killed_proposed, 0)
    return numbers


def settled_epochs(obs: Dict) -> Dict[bytes, int]:
    """tx -> the epoch the first validator settled it in (first
    occurrence): what the latencies are read against."""
    out: Dict[bytes, int] = {}
    for epoch, contributions in enumerate(obs["ledgers"][witness(obs)]):
        for txs in contributions.values():
            for tx in txs:
                out.setdefault(tx, epoch)
    return out


# -- the write-ahead logs ---------------------------------------------------

# core/ledger.py's docstring is the format's specification: records of
# ``magic | u32 len | body | u32 crc32(body)``, all big-endian
WAL_BATCH = b"CLOG"  # u64 epoch | u32 proposers | per proposer, sorted
#                      (u32 | id | u32 txs | per tx (u32 | bytes))
WAL_ORDERED = b"COrd"  # u64 epoch | u32 proposers | per proposer, sorted
#                        (u32 | id | u32 | ciphertext)
# framed alike and skipped by their length: the dedup-set checkpoint,
# the roster switch, and the lane-tagged twins of the three
WAL_SKIPPED = (b"CCKP", b"RCFG", b"LCLG", b"LCKP", b"LOrd")

WalRecord = Tuple[bytes, Optional[int], Optional[Dict[str, List[bytes]]], int]


def _wal_body(magic: bytes, body: bytes) -> Tuple[int, Dict[str, List[bytes]]]:
    """(epoch, {proposer: [tx]}) of a batch record's body, or (epoch,
    {proposer: [ciphertext]}) of an ordered record's."""
    epoch, proposers = struct.unpack_from(">QI", body, 0)
    off = 12
    out: Dict[str, List[bytes]] = {}
    for _ in range(proposers):
        (width,) = struct.unpack_from(">I", body, off)
        off += 4
        proposer = body[off:off + width].decode("utf-8")
        off += width
        count = 1
        if magic == WAL_BATCH:
            (count,) = struct.unpack_from(">I", body, off)
            off += 4
        items = []
        for _ in range(count):
            (width,) = struct.unpack_from(">I", body, off)
            off += 4
            items.append(body[off:off + width])
            off += width
        out[proposer] = items
    if off != len(body):
        raise ValueError("the body's lengths do not add up to its own")
    return epoch, out


def wal_records(data: bytes) -> Tuple[List[WalRecord], int]:
    """The whole records at the head of ``data`` as (magic, epoch,
    contributions, the offset at which the record ends), epoch and
    contributions None for a kind that is skipped; and the offset at
    which the last of them ends.  It stops, as a validator that
    recovers from the file does, at the first record with an unknown
    magic, a length past the end, a CRC that does not match or a body
    that does not parse: what follows is a torn tail."""
    records: List[WalRecord] = []
    off = 0
    while off + 8 <= len(data):
        magic = data[off:off + 4]
        (width,) = struct.unpack_from(">I", data, off + 4)
        end = off + 8 + width + 4
        if end > len(data):
            break
        body = data[off + 8:end - 4]
        if zlib.crc32(body) != struct.unpack_from(">I", data, end - 4)[0]:
            break
        if magic in (WAL_BATCH, WAL_ORDERED):
            try:
                epoch, contributions = _wal_body(magic, body)
            except (ValueError, struct.error):  # UnicodeDecodeError is one
                break
            records.append((magic, epoch, contributions, end))
        elif magic in WAL_SKIPPED:
            records.append((magic, None, None, end))
        else:
            break
        off = end
    return records, off


def compare_wal(obs: Dict) -> Numbers:
    """``obs``: as ``compare_served`` takes it, and ``wal``:
    durable_replicas, logs {node_id: {path, held_bytes,
    held_at_settle}}.  Every log is read here, ``held_bytes`` of it: the
    prefix a crash after the drain would leave.  ``held_at_settle[e]``
    is the prefix a crash would have left at the moment epoch e was
    stamped settled, and a record counts for its epoch only if it ends
    inside that: a settle that is acknowledged before its records are
    held is not durable, whatever reaches the file once the system is
    idle.  (An epoch with no stamp, one a validator settled ahead of
    the slowest, is judged by ``held_bytes``.)

    Under a fault schedule a log also has ``served_at_settle`` (was its
    validator in service when epoch e was stamped settled?) and
    ``adopted`` ([from, to) epochs a restarted validator may have taken
    over from its peers: from where its log left it to the last epoch
    they had ordered or in flight when it came level).  A log is held to a settle stamp
    (``wal_short``, ``wal_late``) only where its validator was in
    service then: a validator that is down acknowledges nothing.  At
    rest every log is held in full (``wal_missing``, ``wal_wrong``,
    ``wal_torn``): a restarted validator's log has every epoch it
    settled, byte for byte the agreed batch.  For an adopted epoch
    ``core/ledger.py`` promises the CLOG record ("appended on every
    commit, local or adopted via catch-up") and no COrd record of the
    validator's own (the ordering was never run there): so
    ``wal_unordered`` asks of an adopted epoch only that a COrd record,
    where there is one (the epoch was ordered before the kill and
    settled after the restart, or its ordering was adopted ahead of its
    plaintext), comes before its CLOG record.

      wal_short      transactions OK-acked and settled whose batch
                     record was held in time in fewer than
                     ``durable_replicas`` logs
      wal_missing    (validator, settled epoch) with no whole CLOG
                     record in ``held_bytes``
      wal_late       (validator, settled epoch) whose CLOG or COrd
                     record is in ``held_bytes`` and ends beyond the
                     prefix held when the epoch was stamped settled
      wal_wrong      CLOG records that are not, proposer for proposer
                     and byte for byte, the batch that validator settled
                     for that epoch, or name a proposer their epoch's
                     COrd record does not; of epochs never settled, out
                     of order or twice; COrd records that differ from
                     the one another validator holds for the epoch
                     (``core/ledger.py``: "honest nodes' ordered logs
                     are byte-identical")
      wal_unordered  settled epochs whose COrd record is absent from
                     ``held_bytes`` or follows its CLOG record
      wal_torn       bytes of ``held_bytes`` after its last whole record
    """
    wal = obs["wal"]
    ids = list(obs["node_ids"])
    admitted = {tx for tx, _node, ok in obs["submissions"] if ok}
    copies = {
        tx: 0
        for contributions in obs["ledgers"][witness(obs)]
        for txs in contributions.values() for tx in txs if tx in admitted
    }
    missing = late = wrong = unordered = torn = 0
    agreed: Dict[int, Dict[str, List[bytes]]] = {}  # epoch -> its COrd body
    for nid in ids:
        log = wal["logs"][nid]
        with open(log["path"], "rb") as fh:
            held = fh.read(log["held_bytes"])
        records, end = wal_records(held)
        torn += len(held) - end
        ledger = obs["ledgers"][nid]
        stamped = log["held_at_settle"]
        served = log.get("served_at_settle")
        adopted = log.get("adopted", ())

        def in_time(epoch: int, ends_at: int) -> bool:
            return epoch >= len(stamped) or ends_at <= stamped[epoch]

        def held_to_stamp(epoch: int) -> bool:
            return served is None or epoch >= len(served) or served[epoch]

        # epoch -> (place, end) of its first record of the kind
        batch_at: Dict[int, Tuple[int, int]] = {}
        ordered_at: Dict[int, Tuple[int, int]] = {}
        ordered: Dict[int, Dict[str, List[bytes]]] = {}
        read_back = set()
        newest = -1
        for place, (magic, epoch, contributions, ends_at) in enumerate(records):
            if magic == WAL_ORDERED:
                if agreed.setdefault(epoch, contributions) != contributions:
                    wrong += 1
                ordered_at.setdefault(epoch, (place, ends_at))
                ordered.setdefault(epoch, contributions)
            elif magic == WAL_BATCH:
                # an epoch with no COrd record is wal_unordered's to count
                may_propose = ordered.get(epoch, contributions)
                if (epoch <= newest or epoch >= len(ledger)
                        or contributions != ledger[epoch]
                        or any(p not in may_propose for p in contributions)):
                    wrong += 1
                newest = max(newest, epoch)
                if epoch not in batch_at:
                    batch_at[epoch] = (place, ends_at)
                    if held_to_stamp(epoch) and in_time(epoch, ends_at):
                        for txs in contributions.values():
                            read_back.update(txs)
        for epoch in range(len(ledger)):
            batch, first = batch_at.get(epoch), ordered_at.get(epoch)
            if batch is None:
                missing += 1
            if first is None:
                if not any(lo <= epoch < hi for lo, hi in adopted):
                    unordered += 1
            elif batch is not None and first[0] > batch[0]:
                unordered += 1
            if held_to_stamp(epoch) and any(
                at is not None and not in_time(epoch, at[1])
                for at in (batch, first)
            ):
                late += 1
        for tx in read_back:
            if tx in copies:
                copies[tx] += 1
    short = sum(1 for n in copies.values() if n < wal["durable_replicas"])
    return {
        "wal_short": (short, 0),
        "wal_missing": (missing, 0),
        "wal_late": (late, 0),
        "wal_wrong": (wrong, 0),
        "wal_unordered": (unordered, 0),
        "wal_torn": (torn, 0),
    }


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


__all__ = ["compare_served", "compare_wal", "wal_records", "compare_lockstep",
           "settled_epochs", "witness", "predict_batch", "CoinReference",
           "verdict", "Numbers"]
