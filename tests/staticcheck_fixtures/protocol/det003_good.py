"""DET003 known-good: protocol clients stage crypto work and offer it
through the hub's drain protocol; justified inline checks carry
allow[DET003] pragmas; and names that merely LOOK like crypto calls
(a local helper, bytes.decode) must not trip the rule."""


class WaveClient:
    def __init__(self, hub):
        self.hub = hub
        self._pending_echo = []
        self._staged_decodes = []

    def handle_echo(self, root, leaf, branch, index, sender):
        # the columnar discipline: park the proof, mark dirty, let the
        # hub's wave drain and batch it
        self._pending_echo.append((root, leaf, branch, index, sender))
        self.hub.mark_dirty(self)

    def drain_pending(self, wave):
        for root, leaf, branch, index, sender in self._pending_echo:
            wave.add_branch(self, root, leaf, branch, index, sender)
        self._pending_echo = []
        for root, idxs, shards, cb in self._staged_decodes:
            wave.add_decode(root, idxs, shards, cb)
        self._staged_decodes = []

    def precheck_val(self, crypto, root, leaf, branch, index):
        return crypto.merkle.verify_branch(  # staticcheck: allow[DET003] inline VAL check
            root, leaf, branch, index
        )

    def parse_frame(self, raw: bytes) -> str:
        # bytes.decode is text decoding, not an RS dispatch
        return raw.decode("utf-8")

    def decode_batch_label(self, rows):
        # a local helper that happens to share a hazard name is fine
        # when it is plain data shaping, not a crypto object's method
        return [f"row-{r}" for r in rows]


class BankClient:
    """The EchoBank discipline (ISSUE 9, 35): pending proofs park in
    the bank as frame records, in arrival order, and cross WHOLE into
    the hub wave's branch column — no inline verify anywhere on the
    receive path."""

    def __init__(self, hub, bank, index):
        self.hub = hub
        self.bank = bank
        self.index = index

    def echo_item(self, frame):
        self.bank.parked.append(frame)
        self.bank.has_parked[self.index] = True
        self.hub.mark_dirty(self)

    def drain_pending(self, wave):
        parked, self.bank.parked = self.bank.parked, []
        for frame in parked:
            wave.add_branch_frame(frame)
        self.bank.has_parked[self.index] = False
        wave.note_branch_client(self)
