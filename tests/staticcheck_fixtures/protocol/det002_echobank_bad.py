"""Known-bad DET002 corpus for the EchoBank surface (ISSUE 9): the
delivery-plane bank keeps receipt state in arrays and insertion-
ordered dicts precisely so no set order ever reaches protocol
decisions — a hand-rolled bank that iterates its sender/root SETS in
hash order must still gate.  Every tagged line is the exact shape the
real protocol.echobank avoids (its registry is a dict, its parked
frames a list in arrival order)."""


class BadEchoBank:
    """An EchoBank-alike that leaks PYTHONHASHSEED order."""

    def __init__(self):
        # receipt state as sets — the pre-bank dict-of-dicts shape
        self.echo_senders = set()
        self.ready_roots: set = set()
        self.parked = {}

    def drain_slots(self, wave):
        # hash-order drain: wave column order would differ across
        # PYTHONHASHSEED values (the regression DET002 exists for)
        for sender in self.echo_senders:  # BAD:DET002
            wave.add(sender)

    def quorum_roots(self):
        return [r for r in self.ready_roots]  # BAD:DET002

    def first_root(self):
        candidates = {b"r1", b"r2"}
        ordered = list(candidates)  # BAD:DET002
        return ordered[0]

    def relay_order(self):
        crossings = frozenset(("a", "b"))
        return max(crossings)  # BAD:DET002
