"""known-bad ARM001: an arm registry declaring a flag that is not a
bool Config field (a stale entry naming a removed option), a flag
nothing ever reads (a dead arm: nothing selects on it), and a live
flag that is missing from the perfgate fingerprint (a mode flip would
gate against the other mode's trend records)."""

import dataclasses

ARM_FLAGS = ("ab_removed_arm", "ab_dead_arm", "ab_unkeyed_arm")  # BAD:ARM001


@dataclasses.dataclass
class Config:
    ab_dead_arm: bool = True  # BAD:ARM001
    ab_unkeyed_arm: bool = True  # BAD:ARM001
    batch: int = 8


def record(cfg):
    return {
        "fingerprint": {"batch": cfg.batch, "ab_dead_arm": True},
        "fast": bool(cfg.ab_unkeyed_arm),
    }
