"""Shared by the WAL readers: the three spans ``core/ledger.py`` puts
around a record's encode-to-flush on the commit path
(``ledger/wal_ordered``: the COrd record, ``ledger/wal_append``: the
CLOG record, ``ledger/wal_checkpoint``: the dedup-set checkpoint every
``ledger_checkpoint_every`` commits)."""

SPANS = ("ledger/wal_append", "ledger/wal_ordered", "ledger/wal_checkpoint")
